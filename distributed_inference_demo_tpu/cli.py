"""Command-line apps: ``serve`` / ``worker`` / ``plan`` / ``generate`` /
``bench``.

Replaces the reference's entry points (SURVEY.md §7.9): ``server.py``'s
``__main__`` block + HTTP stub (``server.py:583-1052``), ``client.py``'s
argparse worker (``client.py:179-190``), and the Android
``BackgroundService`` driver — as one console tool:

    python -m distributed_inference_demo_tpu serve --model tinyllama-1.1b
    python -m distributed_inference_demo_tpu serve --model llama-test \\
        --chain w1@127.0.0.1:7001,w2@127.0.0.1:7002 --elastic
    python -m distributed_inference_demo_tpu worker --model llama-test ...
    python -m distributed_inference_demo_tpu plan --model llama-3-8b \\
        --devices devices.json --save plan.json
    python -m distributed_inference_demo_tpu generate --model llama-test \\
        --prompt-ids 1,2,3 --max-new-tokens 8 --greedy
    python -m distributed_inference_demo_tpu bench --model tinyllama-1.1b
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional


def _load_tokenizer(path: Optional[str]):
    if not path:
        return None
    from .tokenizer import Tokenizer
    # auto-detects sentencepiece .model protobufs vs HF tokenizer.json
    return Tokenizer.from_file(path)


def _load_full_params(args, cfg, mesh=None):
    """Resolve the full parameter tree for a CLI invocation: checkpoint if
    ``--checkpoint`` was given, else seed-init (int8-quantized during init
    for ``-int8`` configs); under a tp ``mesh`` the tree arrives sharded
    in the engines' layout (seeded weights are born on their shards).
    Shared by the single-node and ``--chain`` serve paths so a checkpoint
    can never be silently ignored on one of them."""
    from .models.loader import load_or_init

    return load_or_init(args.model, cfg, getattr(args, "checkpoint", None),
                        seed=args.weights_seed, mesh=mesh)


def _sampling_from_args(args):
    """The one mapping from CLI flags to SamplingParams — shared by every
    serve mode so a new sampling flag cannot silently diverge between
    single-node, --chain, and --batch-slots."""
    from .ops.sampling import SamplingParams
    if args.greedy:
        return SamplingParams(greedy=True)
    return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          min_p=getattr(args, "min_p", 0.0))


def _tp_mesh_from_args(args):
    """tp mesh from the --tp flag (parallel.mesh owns the rule)."""
    from .parallel.mesh import local_tp_mesh
    return local_tp_mesh(getattr(args, "tp", 1))


def _load_params_for_mesh(args, cfg):
    """(params, mesh): checkpoint-or-seed params, sharded onto the --tp
    mesh when one is requested — the one load+shard sequence shared by
    every engine builder."""
    mesh = _tp_mesh_from_args(args)
    return _load_full_params(args, cfg, mesh), mesh


def _load_draft_for_mesh(args, mesh):
    """(draft_cfg, draft_params) from the --draft-model/--draft-checkpoint
    flags, sharded onto ``mesh`` when serving tensor-parallel — shared by
    the standalone speculative engine and the batching composition."""
    from .models.registry import get_model_config

    draft_cfg = get_model_config(args.draft_model)
    draft_params = _load_full_params(
        argparse.Namespace(**{**vars(args),
                              "model": args.draft_model,
                              "checkpoint": args.draft_checkpoint}),
        draft_cfg, mesh)
    return draft_cfg, draft_params


def _kvcache_from_args(args):
    """``(kv_cache_blocks, kv_block_tokens)`` engine kwargs from the CLI
    flags (None = not given: the engine falls back to DWT_KVCACHE_* env
    knobs, then its own default) — one mapping shared by every engine
    builder so the block-cache flags cannot silently diverge between
    serve modes."""
    return {"kv_cache_blocks": getattr(args, "kv_cache_blocks", None),
            "kv_block_tokens": getattr(args, "kv_block_tokens", None)}


def _kv_tier_from_args(args):
    """The §21 tiered-KV kwargs for the one engine that plumbs them
    explicitly (ContinuousBatchingEngine).  Every OTHER engine reaches
    the tier through ``make_kv_backend``'s env fallback, which is why
    :func:`_export_kv_tier_env` pushes the flags into the ``DWT_KV_*``
    knobs instead of threading three kwargs through every ctor."""
    return {"kv_host_tier_bytes": getattr(args, "kv_host_tier_bytes",
                                          None),
            "kv_disk_tier_path": getattr(args, "kv_disk_tier_path",
                                         None) or None,
            "kv_disk_tier_bytes": getattr(args, "kv_disk_tier_bytes",
                                          None)}


def _export_kv_tier_env(args) -> None:
    """Arg-over-env, via env: the tier flags overwrite their own env
    knobs so ``resolve_tier_config`` (called inside ``make_kv_backend``
    at every pool-creation site) sees the CLI's values — the §17
    kv_dtype funnel pattern, flag wins, zero per-engine plumbing."""
    if getattr(args, "kv_host_tier_bytes", None) is not None:
        os.environ["DWT_KV_HOST_TIER_BYTES"] = str(
            args.kv_host_tier_bytes)
    if getattr(args, "kv_disk_tier_path", None):
        os.environ["DWT_KV_DISK_TIER_PATH"] = args.kv_disk_tier_path
    if getattr(args, "kv_disk_tier_bytes", None) is not None:
        os.environ["DWT_KV_DISK_TIER_BYTES"] = str(
            args.kv_disk_tier_bytes)


def _kvcache_flags_set(args) -> bool:
    """Did the user EXPLICITLY ask for the block cache?  Unset/0 is not
    a request (0 is 'off' everywhere) — the one condition every
    unsupported-mode rejection keys on, so no mode can accept one of
    the pair and silently drop the other."""
    return bool(getattr(args, "kv_cache_blocks", None)
                or getattr(args, "kv_block_tokens", None))


def _reject_kvcache_flags(args, mode: str) -> bool:
    """True (after printing) when the kv-cache flags were explicitly set
    for a mode with no block-cache plumbing — honor-or-reject, never
    silently ignore."""
    if _kvcache_flags_set(args):
        print("--kv-cache-blocks/--kv-block-tokens are not supported "
              f"with {mode}", file=sys.stderr)
        return True
    return False


def _build_spec_engine(args):
    """Construct the draft/verify SpeculativeEngine from CLI flags — the
    one site shared by ``generate --draft-model`` and
    ``serve --draft-model``.  Every engine flag composes here
    (--kv-cache-dtype, --prefill-chunk, --tp, --eos-id,
    --kv-cache-blocks)."""
    from .models.registry import get_model_config
    from .runtime import SpeculativeEngine

    if getattr(args, "stream_block", None) is not None:
        raise ValueError(
            "--stream-block is not supported with --draft-model "
            "(the draft/verify round is already the fused dispatch "
            "unit)")
    cfg = get_model_config(args.model)
    params, mesh = _load_params_for_mesh(args, cfg)
    draft_cfg, draft_params = _load_draft_for_mesh(args, mesh)
    return SpeculativeEngine(
        cfg, params, draft_cfg, draft_params,
        max_seq=args.max_seq, sampling=_sampling_from_args(args),
        num_draft=args.num_draft, attn_backend=args.attn_backend,
        mesh=mesh, eos_id=getattr(args, "eos_id", None),
        kv_cache_dtype=getattr(args, "kv_cache_dtype", None) or None,
        prefill_chunk=getattr(args, "prefill_chunk", 0) or None,
        kv_dtype=getattr(args, "kv_dtype", None),
        **_kvcache_from_args(args))


def _build_prompt_lookup_engine(args):
    """Construct the draft-free PromptLookupEngine from CLI flags — the one
    site shared by ``generate --prompt-lookup`` and
    ``serve --prompt-lookup``.  Every engine flag composes here
    (--kv-cache-dtype, --prefill-chunk, --tp, --eos-id)."""
    from .models.registry import get_model_config
    from .runtime.prompt_lookup import PromptLookupEngine

    if getattr(args, "stream_block", None) is not None:
        raise ValueError(
            "--stream-block is not supported with --prompt-lookup "
            "(the n-gram draft/verify round is already the fused "
            "dispatch unit)")
    cfg = get_model_config(args.model)
    params, mesh = _load_params_for_mesh(args, cfg)
    return PromptLookupEngine(
        cfg, params, max_seq=args.max_seq,
        sampling=_sampling_from_args(args), num_draft=args.num_draft,
        attn_backend=args.attn_backend, mesh=mesh,
        eos_id=getattr(args, "eos_id", None),
        kv_cache_dtype=getattr(args, "kv_cache_dtype", None) or None,
        prefill_chunk=getattr(args, "prefill_chunk", 0) or None,
        kv_dtype=getattr(args, "kv_dtype", None),
        **_kvcache_from_args(args))


def _build_engine(args):
    from .models.registry import get_model_config
    from .runtime import InferenceEngine

    cfg = get_model_config(args.model)
    sampling = _sampling_from_args(args)
    # tensor-parallel serving (BASELINE config #3): Megatron-sliced
    # weights + kv-head-sharded cache over the first tp local devices
    params, mesh = _load_params_for_mesh(args, cfg)
    return cfg, InferenceEngine(
        cfg, params, max_seq=args.max_seq, sampling=sampling,
        attn_backend=args.attn_backend,
        kv_cache_dtype=getattr(args, "kv_cache_dtype", None) or None,
        prefill_chunk=getattr(args, "prefill_chunk", 0) or None,
        stream_block=getattr(args, "stream_block", None),
        mesh=mesh, eos_id=getattr(args, "eos_id", None),
        kv_dtype=getattr(args, "kv_dtype", None),
        **_kvcache_from_args(args))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def cmd_serve(args) -> int:
    """Single-node engine serving, or pipeline-header serving over a worker
    chain (start the workers first with the ``worker`` subcommand)."""
    from .runtime.http_server import HeaderBackend, InferenceHTTPServer

    _export_kv_tier_env(args)
    if getattr(args, "run_log", ""):
        from .telemetry.runlog import RunLog, set_run_log
        rl = RunLog(args.run_log)
        set_run_log(rl)
        rl.event("serve_start", model=args.model,
                 max_seq=args.max_seq,
                 chain=bool(args.chain),
                 batch_slots=getattr(args, "batch_slots", 0))
    # black-box capture for this serving process: anomaly/stall triggers
    # and unhandled crashes dump bundles.  --postmortem-dir installs the
    # writer explicitly; DWT_POSTMORTEM_DIR alone is honored too (the
    # lazy get below resolves it), and EITHER configuration gets the
    # crash handler — env-only capture must not silently lose crashes
    from .telemetry import postmortem
    if getattr(args, "postmortem_dir", ""):
        postmortem.set_postmortem_writer(
            postmortem.PostmortemWriter(args.postmortem_dir))
    if postmortem.get_postmortem_writer() is not None:
        postmortem.install_crash_handler(config={
            k: v for k, v in vars(args).items() if k != "fn"})

    modes = [name for name, on in [("--chain", args.chain),
                                   ("--draft-model",
                                    getattr(args, "draft_model", "")),
                                   ("--prompt-lookup",
                                    getattr(args, "prompt_lookup", False)),
                                   ("--batch-slots",
                                    getattr(args, "batch_slots", 0)),
                                   ("--sp",
                                    getattr(args, "sp", 1) > 1),
                                   ("--vision",
                                    getattr(args, "vision", False))] if on]
    # --batch-slots composes with --draft-model OR --prompt-lookup
    # (speculative decoding inside the slot loop — the production serving
    # shape); every other pairing stays an explicit error
    if len(modes) > 1 and set(modes) not in (
            {"--batch-slots", "--draft-model"},
            {"--batch-slots", "--prompt-lookup"}):
        print(f"choose one serve mode, got {' + '.join(modes)}",
              file=sys.stderr)
        return 1
    if getattr(args, "no_spec_adaptive", False) and not (
            getattr(args, "batch_slots", 0)
            and ("--draft-model" in modes or "--prompt-lookup" in modes)):
        # adaptive K_row lives in the mixed slot loop; anywhere else the
        # flag would silently do nothing
        print("--no-spec-adaptive requires --batch-slots with "
              "--draft-model or --prompt-lookup", file=sys.stderr)
        return 1
    if getattr(args, "tp", 1) > 1 and "--chain" in modes:
        print("--tp is not supported with --chain (stages are whole-model "
              "slices per worker)", file=sys.stderr)
        return 1
    if getattr(args, "pool_size", 1) > 1 and not args.chain:
        # reject loudly rather than silently serializing requests
        print("--pool-size requires --chain (pipeline dynamic batching); "
              "--batch-slots is the single-node batching mode",
              file=sys.stderr)
        return 1

    # chaos fault plan: resolved EARLY so a leaked DWT_FAULT_PLAN env var
    # kills the process at startup instead of silently injecting faults
    from .comm.faults import FaultConfigError, load_fault_plan, maybe_wrap
    try:
        fault_plan = load_fault_plan(getattr(args, "fault_plan", ""),
                                     getattr(args, "chaos", False))
    except FaultConfigError as e:
        print(str(e), file=sys.stderr)
        return 1
    if fault_plan is not None and not args.chain:
        print("--fault-plan applies to the data-plane transport and "
              "requires --chain (single-process engine modes have no "
              "transport to fault)", file=sys.stderr)
        return 1

    tokenizer = _load_tokenizer(args.tokenizer)

    if args.chain:
        import jax

        from .comm.transport import ZmqTransport
        from .models.base import (require_kv_pair, require_one_kind,
                                  require_token_rows,
                                  require_single_pass,
                                  split_layer_ranges)
        from .models.registry import get_model_config
        from .runtime.elastic import ElasticHeader, ElasticStageRuntime

        cfg = get_model_config(args.model)
        try:
            require_single_pass(cfg, "--chain (a pipeline of stages)")
            require_kv_pair(cfg, "--chain (a pipeline of stages)")
            require_token_rows(cfg, "--chain (a pipeline of stages)")
            require_one_kind(cfg, "--chain (a pipeline of stages)")
        except ValueError as e:
            print(e, file=sys.stderr)
            return 1
        if getattr(args, "prefill_chunk", 0):
            print("--prefill-chunk is not supported with --chain",
                  file=sys.stderr)
            return 1
        if getattr(args, "stream_block", None) is not None:
            # the ring's topology caps a circuit at one token (DESIGN
            # §13: the tail fuses forward+sample instead); honor-or-
            # reject, never silently ignore
            print("--stream-block is not supported with --chain",
                  file=sys.stderr)
            return 1
        if _reject_kvcache_flags(args, "--chain (pipeline stages see "
                                 "activations, not tokens — there is "
                                 "no prompt key to match blocks by)"):
            return 1
        full = _load_full_params(args, cfg)
        sampling = _sampling_from_args(args)

        peers = [p.split("@", 1) for p in args.chain.split(",")]
        chain = [args.device_id] + [pid for pid, _ in peers]
        specs = split_layer_ranges(cfg.num_layers, len(chain))
        transport = maybe_wrap(
            ZmqTransport(args.device_id, bind_host=args.bind_host,
                         port=args.port), fault_plan)
        for pid, addr in peers:
            transport.connect(pid, addr)
        # the header's own stage honors --kv-cache-dtype; chain workers
        # take their own --kv-cache-dtype flag (each stage's cache is its
        # own business — the wire carries activations, not cache state)
        rt = ElasticStageRuntime(
            cfg, specs[0], full, args.max_seq, sampling,
            kv_cache_dtype=getattr(args, "kv_cache_dtype", "") or None)
        header = ElasticHeader(rt, transport, chain,
                               eos_id=getattr(args, "eos_id", None),
                               step_timeout=args.step_timeout)
        # initial reshard pushes the authoritative layer plan to the chain —
        # workers may start with any placeholder range (cli worker --elastic
        # defaults to the full model) and are aligned here.
        header.reshard(chain)
        pool = getattr(args, "pool_size", 1)
        if pool > 1:
            # dynamic batching: concurrent HTTP requests group into
            # generate_many windows with pool_size rids interleaving
            # through the stages (runtime/dynamic_batch.py)
            from .runtime.dynamic_batch import DynamicBatchingHeaderBackend
            backend = DynamicBatchingHeaderBackend(
                header, max_seq=args.max_seq, num_stages=len(chain),
                pool_size=pool)
        else:
            backend = HeaderBackend(header, max_seq=args.max_seq,
                                    num_stages=len(chain))
        kv_dtype = getattr(args, "kv_cache_dtype", "") or None
        if kv_dtype:
            # each stage owns its cache dtype; this flag reaches only the
            # header's stage — say so loudly, or a chain whose workers
            # weren't launched with their own --kv-cache-dtype silently
            # keeps full-precision caches on every other host
            print(f"note: --kv-cache-dtype={kv_dtype} applies to the "
                  "header stage only; start each worker with its own "
                  "--kv-cache-dtype to reduce its cache too",
                  file=sys.stderr)
        print(f"SERVE_PIPELINE {chain} ranges="
              f"{[(s.layer_start, s.layer_end) for s in specs]}"
              + (f" header_kv_cache_dtype={kv_dtype}" if kv_dtype else ""),
              flush=True)
    elif getattr(args, "sp", 1) > 1:
        # long-context serving: ring/Ulysses sequence parallelism behind
        # the same HTTP surface (runtime/sp_backend.py); --tp is covered
        # by the mode exclusivity above only for other MODES, so guard
        # the mesh conflict explicitly
        from .models.registry import get_model_config
        from .parallel.mesh import local_sp_mesh
        from .runtime.sp_backend import SequenceParallelBackend

        if getattr(args, "tp", 1) > 1:
            print("--sp is exclusive with --tp", file=sys.stderr)
            return 1
        unsupported = _sp_unsupported_flags(args, allow_eos=True)
        if unsupported:
            print(f"{'/'.join(unsupported)} not supported with --sp",
                  file=sys.stderr)
            return 1
        cfg = get_model_config(args.model)
        mesh = local_sp_mesh(args.sp)
        params = _load_full_params(args, cfg)
        backend = SequenceParallelBackend(
            cfg, params, mesh, max_seq=args.max_seq,
            strategy=args.sp_strategy, sampling=_sampling_from_args(args),
            kv_cache_dtype=getattr(args, "kv_cache_dtype", None) or None,
            eos_id=getattr(args, "eos_id", None),
            max_queue_depth=getattr(args, "sp_queue_depth", None))
        print(f"SERVE_SP {args.model} sp={args.sp} "
              f"strategy={args.sp_strategy} max_seq={args.max_seq}",
              flush=True)
    elif getattr(args, "vision", False):
        # LLaVA-style multimodal serving: ViT tower + projector in front
        # of the decoder; /generate takes an optional "image" field and
        # text-only requests run the plain engine path unchanged
        import jax as _jax

        from .models.registry import get_model_config
        from .models.vision import VisionConfig, init_vision_params
        from .runtime.multimodal import MultimodalBackend, MultimodalEngine

        unsupported = [flag for flag, on in [
            ("--kv-cache-dtype", bool(getattr(args, "kv_cache_dtype", ""))),
            ("--prefill-chunk", bool(getattr(args, "prefill_chunk", 0))),
            ("--stream-block",
             getattr(args, "stream_block", None) is not None),
            ("--kv-cache-blocks", _kvcache_flags_set(args)),
            ("--tp", getattr(args, "tp", 1) > 1)] if on]
        if unsupported:
            print(f"{'/'.join(unsupported)} not supported with --vision",
                  file=sys.stderr)
            return 1
        cfg = get_model_config(args.model)
        if args.vision_preset == "llava15":
            # the CLIP-ViT-L/14-336 geometry LLaVA-1.5 ships, faithful:
            # class token, pre-layernorm, projection biases, quick_gelu,
            # penultimate-layer feature select — HF CLIP/LLaVA vision
            # checkpoints load via --vision-checkpoint without
            # reinterpretation
            vcfg = VisionConfig(image_size=336, patch_size=14,
                                hidden_size=1024, num_layers=24,
                                num_heads=16, intermediate_size=4096,
                                dtype_name="bfloat16", clip_arch=True,
                                feature_layer=-2, hidden_act="quick_gelu")
        elif args.vision_preset == "clip-test":
            # tiny faithful tower for tests/drives (same arch flags as
            # llava15, checkpoint-loadable at toy scale)
            vcfg = VisionConfig(image_size=28, patch_size=14,
                                hidden_size=32, num_layers=3,
                                num_heads=4, intermediate_size=64,
                                dtype_name="float32", clip_arch=True,
                                feature_layer=-2, hidden_act="quick_gelu")
        else:     # "small": a CLIP-base-like tower for modest decoders
            vcfg = VisionConfig(image_size=224, patch_size=14,
                                hidden_size=256, num_layers=6,
                                num_heads=8, intermediate_size=1024,
                                dtype_name="bfloat16")
        params = _load_full_params(args, cfg)
        if getattr(args, "vision_checkpoint", ""):
            from .models.loader import load_vision_params
            vparams = load_vision_params(args.vision_checkpoint, vcfg,
                                         cfg.hidden_size,
                                         seed=args.weights_seed)
        else:
            # without a checkpoint the tower is seeded random init; the
            # geometry and serving surface are real.  Seeded from
            # --weights-seed like every other weight init, so the same
            # seed reproduces the model regardless of the sampling --seed
            vparams = init_vision_params(
                _jax.random.PRNGKey(args.weights_seed), vcfg,
                cfg.hidden_size)
        backend = MultimodalBackend(MultimodalEngine(
            cfg, params, vcfg, vparams, max_seq=args.max_seq,
            sampling=_sampling_from_args(args),
            eos_id=getattr(args, "eos_id", None),
            attn_backend=args.attn_backend,
            kv_dtype=getattr(args, "kv_dtype", None)))
        print(f"SERVE_VISION {args.model} tower={args.vision_preset} "
              f"image={vcfg.image_size} patches={vcfg.num_patches}",
              flush=True)
    elif getattr(args, "batch_slots", 0):
        from .models.registry import get_model_config
        from .runtime.batching import ContinuousBatchingEngine

        if getattr(args, "stream_block", None) is not None:
            # the scheduler's fused block is --decode-block; a second K
            # knob must be rejected, never silently ignored
            print("--stream-block is not supported with --batch-slots "
                  "(use --decode-block)", file=sys.stderr)
            return 1
        cfg = get_model_config(args.model)
        sampling = _sampling_from_args(args)
        params, mesh = _load_params_for_mesh(args, cfg)
        draft_cfg = draft_params = None
        if getattr(args, "draft_model", ""):
            # speculative decoding inside the slot loop
            draft_cfg, draft_params = _load_draft_for_mesh(args, mesh)
        pld = bool(getattr(args, "prompt_lookup", False))
        backend = ContinuousBatchingEngine(
            cfg, params, max_seq=args.max_seq,
            max_batch=args.batch_slots, sampling=sampling, seed=args.seed,
            mesh=mesh,
            kv_cache_dtype=getattr(args, "kv_cache_dtype", None) or None,
            eos_id=getattr(args, "eos_id", None),
            draft_cfg=draft_cfg, draft_params=draft_params,
            num_draft=args.num_draft, prompt_lookup=pld,
            spec_adaptive=not getattr(args, "no_spec_adaptive", False),
            decode_block=args.decode_block,
            prefill_chunk=getattr(args, "prefill_chunk", 0) or None,
            mixed_token_budget=getattr(args, "mixed_token_budget", 0)
            or None,
            kv_dtype=getattr(args, "kv_dtype", None),
            max_queue_depth=getattr(args, "admission_queue_depth", 0),
            **_kvcache_from_args(args), **_kv_tier_from_args(args))
        kvc = backend.kv_cache
        kv_desc = f"{kvc.num_blocks}x{kvc.block_tokens}tok"
        print(f"SERVE_BATCHING {args.model} slots={args.batch_slots} "
              f"kv_cache={kv_desc} "
              f"tp={getattr(args, 'tp', 1)}"
              + (f" draft={args.draft_model} k={args.num_draft}"
                 if draft_cfg is not None else "")
              + (f" prompt_lookup k={args.num_draft}" if pld else "")
              + (" k_adaptive" if (draft_cfg is not None or pld)
                 and not getattr(args, "no_spec_adaptive", False) else ""),
              flush=True)
    elif getattr(args, "draft_model", ""):
        from .runtime.speculative import SpeculativeBackend

        backend = SpeculativeBackend(_build_spec_engine(args))
        print(f"SERVE_SPECULATIVE {args.model} draft={args.draft_model} "
              f"k={args.num_draft}", flush=True)
    elif getattr(args, "prompt_lookup", False):
        from .runtime.speculative import SpeculativeBackend

        backend = SpeculativeBackend(_build_prompt_lookup_engine(args))
        print(f"SERVE_PROMPT_LOOKUP {args.model} k={args.num_draft}",
              flush=True)
    else:
        cfg, engine = _build_engine(args)
        backend = engine
        print(f"SERVE_ENGINE {args.model} attn={engine.attn_backend}",
              flush=True)

    server = InferenceHTTPServer(backend, host=args.http_host,
                                 port=args.http_port, tokenizer=tokenizer,
                                 model_name=args.model,
                                 default_max_new=args.max_new_tokens,
                                 request_timeout=getattr(
                                     args, "request_timeout", 0.0) or None)
    print(f"HTTP_READY http://{server.host}:{server.port}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        if hasattr(backend, "close"):
            # the dynamic-batching/continuous-batching backends run a
            # scheduler thread that must drain its waiters on the way out
            backend.close()
    return 0


# ---------------------------------------------------------------------------
# gateway (replicated serving: cache-aware routing over N replicas)
# ---------------------------------------------------------------------------

def _parse_replicas(spec: str):
    """``host:port,host:port,...`` → ``[(host, port), ...]``."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        host, _, port = part.rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(
                f"bad replica {part!r}: expected host:port")
        out.append((host, int(port)))
    if not out:
        raise ValueError("--replicas needs at least one host:port")
    return out


def cmd_gateway(args) -> int:
    """The prefix-aware replicated serving gateway (docs/DESIGN.md §16):
    spread /generate traffic across N independent ``serve`` replicas,
    routing each request to the replica most likely to hold its prompt
    prefix in its radix cache.  Holds no engine — start the replicas
    first (``cli serve --batch-slots N ...``), then point the gateway
    at them."""
    from .runtime.gateway import (GatewayHTTPServer, PrefixAwareRouter,
                                  ReplicaRegistry)

    if args.drain or args.undrain:
        # client mode: flip the drain flag on an ALREADY-RUNNING
        # gateway at --http-host/--http-port, print its answer, exit
        import json as _json
        from http.client import HTTPConnection
        rid = args.drain or args.undrain
        body = _json.dumps({"replica": rid,
                            "draining": bool(args.drain)}).encode()
        conn = HTTPConnection(args.http_host, args.http_port, timeout=5.0)
        try:
            conn.request("POST", "/drain", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            print(resp.read().decode("utf-8", "replace"))
            return 0 if resp.status == 200 else 1
        except OSError as e:
            print(f"gateway at {args.http_host}:{args.http_port} "
                  f"unreachable: {e}", file=sys.stderr)
            return 1
        finally:
            conn.close()

    if not args.replicas:
        print("--replicas is required (except with --drain/--undrain)",
              file=sys.stderr)
        return 1
    try:
        replicas = _parse_replicas(args.replicas)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 1
    registry = ReplicaRegistry(
        replicas, sustain=args.evict_sustain,
        readmit_cooldown_s=args.readmit_cooldown,
        probe_interval_s=args.health_interval,
        probe_timeout_s=args.probe_timeout)
    router = PrefixAwareRouter(
        registry, min_prefix_tokens=args.min_prefix_tokens,
        block_tokens=args.route_block_tokens,
        load_factor=args.load_factor)
    server = GatewayHTTPServer(
        registry, router, host=args.http_host, port=args.http_port,
        retry_limit=args.retry_limit,
        resume_limit=args.resume_limit,
        proxy_timeout_s=args.proxy_timeout or None)
    print(f"GATEWAY_READY http://{server.host}:{server.port} "
          f"replicas={','.join(registry.replica_ids())}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
    return 0


# ---------------------------------------------------------------------------
# server (integrated root-server app)
# ---------------------------------------------------------------------------

def cmd_server(args) -> int:
    """The full root-server composition (reference ``server.py:583-1052``):
    collection window → monitor round → cost-model plan → lifecycle
    broadcast with weight-artifact distribution → pipeline header + HTTP."""
    import logging
    logging.basicConfig(level=logging.INFO)
    from .server_app import ServerApp

    if getattr(args, "tp", 1) > 1:
        print("--tp is not supported by the server app (the planner "
              "assigns whole layer ranges per worker)", file=sys.stderr)
        return 1

    app = ServerApp(
        model=args.model, num_workers=args.num_workers,
        checkpoint=args.checkpoint, weights_seed=args.weights_seed,
        max_seq=args.max_seq, max_new_tokens=args.max_new_tokens,
        greedy=args.greedy, temperature=args.temperature, top_k=args.top_k,
        min_p=getattr(args, "min_p", 0.0),
        bind_host=args.bind_host, http_host=args.http_host,
        http_port=args.http_port, collect_window=args.collect_window,
        collect_timeout=args.collect_timeout,
        monitor_timeout=args.monitor_timeout,
        step_timeout=args.step_timeout,
        # broadcast in the OPEN RunConfig, so every auto worker's stage
        # cache uses it too — no mixed-precision pipeline
        kv_cache_dtype=getattr(args, "kv_cache_dtype", "") or None,
        pool_size=args.pool_size)
    return app.run()


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def cmd_worker(args) -> int:
    """One pipeline stage process (see runtime/worker_main.py); ``--elastic``
    makes it reshard-capable (holds full weights, accepts live migration);
    ``--auto`` connects to a ``server`` app and receives its role, layer
    range, and weights from the control plane."""
    from .runtime import worker_main

    if args.auto:
        ap = argparse.ArgumentParser(prog="worker --auto")
        ap.add_argument("--registry", required=True,
                        help="server registration address host:port (the "
                             "only address a bare worker needs)")
        ap.add_argument("--device-id", required=True)
        ap.add_argument("--bind-host", default="127.0.0.1")
        ap.add_argument("--port", type=int, default=0)
        ap.add_argument("--step-timeout", type=float, default=120.0)
        a = ap.parse_args(args.rest)
        from .server_app import run_auto_worker
        return run_auto_worker(a.registry, a.device_id,
                               bind_host=a.bind_host,
                               port=a.port, step_timeout=a.step_timeout)

    if not args.elastic:
        return worker_main.main(args.rest)

    import jax

    from .comm.transport import ZmqTransport
    from .models.base import StageSpec
    from .models.decoder import init_full_params
    from .models.registry import get_model_config
    from .ops.sampling import SamplingParams
    from .runtime.elastic import ElasticStageRuntime, ElasticWorker

    ap = argparse.ArgumentParser(prog="worker --elastic")
    for a in ("--model", "--device-id", "--header"):
        ap.add_argument(a, required=True)
    # stage placement is optional: the serving header pushes the real plan
    # via an initial reshard, so these are placeholders for standalone use.
    ap.add_argument("--stage-id", type=int, default=1)
    ap.add_argument("--num-stages", type=int, default=2)
    ap.add_argument("--layer-start", type=int, default=0)
    ap.add_argument("--layer-end", type=int, default=-1,
                    help="-1 = whole model (placeholder until reshard)")
    ap.add_argument("--bind-host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--next", default="")
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--weights-seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--top-k", type=int, default=7)
    ap.add_argument("--min-p", type=float, default=0.0)
    ap.add_argument("--step-timeout", type=float, default=120.0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor parallelism over this host's first N "
                         "local devices (elastic pipeline x tp)")
    ap.add_argument("--kv-cache-dtype", default="",
                    help="reduced-precision KV cache storage for this "
                         "stage, e.g. float8_e4m3fn")
    ap.add_argument("--fault-plan", default="",
                    help="CHAOS TESTING ONLY: JSON fault-plan spec "
                         "(path or inline); requires --chaos")
    ap.add_argument("--chaos", action="store_true")
    a = ap.parse_args(args.rest)

    from .comm.faults import FaultConfigError, load_fault_plan, maybe_wrap
    try:
        fault_plan = load_fault_plan(a.fault_plan, a.chaos)
    except FaultConfigError as e:
        print(str(e), file=sys.stderr)
        return 1

    cfg = get_model_config(a.model)
    full = init_full_params(jax.random.PRNGKey(a.weights_seed), cfg)
    sampling = SamplingParams(greedy=True) if a.greedy else \
        SamplingParams(temperature=a.temperature, top_k=a.top_k,
                       min_p=a.min_p)
    layer_end = a.layer_end if a.layer_end >= 0 else cfg.num_layers
    spec = StageSpec(a.stage_id, a.num_stages, a.layer_start, layer_end)
    from .parallel.mesh import local_tp_mesh
    rt = ElasticStageRuntime(cfg, spec, full, a.max_seq, sampling,
                             mesh=local_tp_mesh(a.tp),
                             kv_cache_dtype=a.kv_cache_dtype or None)
    transport = maybe_wrap(
        ZmqTransport(a.device_id, bind_host=a.bind_host, port=a.port),
        fault_plan)
    next_id = None
    if a.next:
        next_id, next_addr = a.next.split("@", 1)
        transport.connect(next_id, next_addr)
    header_id, header_addr = a.header.split("@", 1)
    transport.connect(header_id, header_addr)
    worker = ElasticWorker(rt, transport, next_id=next_id,
                           header_id=header_id, step_timeout=a.step_timeout)
    print(f"WORKER_READY {a.device_id} {transport.address}", flush=True)
    try:
        worker.serve_forever()
    finally:
        transport.close()
    return 0


# ---------------------------------------------------------------------------
# chat (streaming REPL client)
# ---------------------------------------------------------------------------

def _parse_url(url: str):
    from urllib.parse import urlparse
    u = urlparse(url if "//" in url else f"http://{url}")
    return u.hostname or "127.0.0.1", u.port or 5000


def stream_generate(host: str, port: int, payload: dict, timeout: float = 600):
    """POST /generate with stream=true; yield each JSONL line as a dict the
    moment its chunk arrives (http.client decodes chunked transfer encoding
    incrementally, so this generator runs concurrently with decoding)."""
    import http.client

    payload = dict(payload, stream=True)
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/generate", body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(
                f"HTTP {resp.status}: {resp.read().decode(errors='replace')}")
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if line:
                yield json.loads(line)
    finally:
        conn.close()


def cmd_chat(args) -> int:
    """Terminal chat REPL over the streaming HTTP endpoint — the reference's
    ChatScreen/DataRepository loop (``ChatScreen.kt:1-353``,
    ``DataRepository.kt:5-27``: partial decodes pushed to the UI as they
    stream, ``Communication.java:629-638``) as a console app.

    Reads one message per line, POSTs ``stream: true``, and renders tokens
    as each chunk arrives.  With ``--ids`` the input line is comma-separated
    token ids (drives tokenizer-less servers, e.g. in tests); otherwise the
    message is wrapped in the reference's prompt template
    (``BackgroundService.java:211``) and tokenized locally (``--tokenizer``)
    or server-side.
    """
    import http.client

    tokenizer = _load_tokenizer(args.tokenizer)
    host, port = _parse_url(args.url)

    print(f"chat -> http://{host}:{port}  (/quit to exit)", flush=True)
    while True:
        sys.stdout.write("> ")
        sys.stdout.flush()
        line = sys.stdin.readline()
        if not line:
            break                       # EOF
        line = line.strip()
        if not line:
            continue
        if line in ("/quit", "/exit"):
            break

        payload = {"max_new_tokens": args.max_new_tokens, "seed": args.seed}
        if getattr(args, "stop", None):
            payload["stop"] = args.stop
        if args.ids:
            try:
                payload["prompt_ids"] = [[int(t) for t in line.split(",")]]
            except ValueError:
                print("[error] --ids mode expects comma-separated ints",
                      file=sys.stderr)
                continue
        else:
            prompt = args.template.format(msg=line)
            if tokenizer is not None:
                payload["prompt_ids"] = [tokenizer.encode(prompt)]
            else:
                payload["prompt"] = prompt   # server-side tokenizer

        try:
            # incremental detokenization (tokenizer.StreamDetokenizer —
            # one owner of the boundary/holdback rules, shared with the
            # server's streaming "text" field)
            from .tokenizer import StreamDetokenizer
            detok = (StreamDetokenizer(tokenizer)
                     if tokenizer is not None else None)
            for item in stream_generate(host, port, payload):
                if "error" in item:
                    # a mid-stream server failure arrives as an error
                    # line; RuntimeError routes it to the REPL's
                    # report-and-continue handler below
                    raise RuntimeError(item["error"])
                if item.get("done"):
                    break              # stop-mode summary line
                if "text" in item:
                    piece = item["text"][0]
                elif detok is not None:
                    piece = detok.push(int(item["tokens"][0]))
                else:
                    piece = ("" if item["step"] == 0 else " ") + \
                        str(item["tokens"][0])
                sys.stdout.write(piece)
                sys.stdout.flush()
            if detok is not None:
                sys.stdout.write(detok.flush())
                sys.stdout.flush()
        except (ConnectionError, OSError, RuntimeError,
                http.client.HTTPException, json.JSONDecodeError) as e:
            # a server dying mid-stream (IncompleteRead, truncated JSONL)
            # must not kill the REPL — report and take the next prompt
            print(f"\n[error] {e}", file=sys.stderr)
            continue
        sys.stdout.write("\n")
        sys.stdout.flush()
    return 0


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

def cmd_plan(args) -> int:
    """Offline partition planning from device profiles (the planner the
    reference commented out, ``server.py:879-891``, made a first-class
    tool)."""
    from .models.registry import get_model_config
    from .planner.cost_model import model_cost_profile
    from .planner.planner import (DeviceProfile, PartitionPlan,
                                  plan_partition, round_robin_plan,
                                  save_plan_cache)

    cfg = get_model_config(args.model)
    if args.load:
        with open(args.load) as f:
            plan = PartitionPlan.from_json(json.load(f))   # validates shape
        if plan.model != args.model:
            print(f"cached plan is for {plan.model!r}, not {args.model!r}",
                  file=sys.stderr)
            return 1
        print(json.dumps(plan.to_json(), indent=2))
        return 0

    with open(args.devices) as f:
        dev_json = json.load(f)
    try:
        devs = [DeviceProfile(**d) for d in dev_json]
    except TypeError as e:
        # a missing flops_per_sec lands here: the planner takes a
        # measured or stated rate, never a default
        print(f"bad device profile in {args.devices}: {e}",
              file=sys.stderr)
        return 1
    if args.round_robin:
        plan = round_robin_plan(cfg, args.model, devs)
    else:
        plan = plan_partition(cfg, args.model, devs, ctx=args.ctx,
                              profile=model_cost_profile(cfg, ctx=args.ctx))
    if args.save:
        save_plan_cache(args.save, plan)
    print(json.dumps(plan.to_json(), indent=2))
    return 0


# ---------------------------------------------------------------------------
# generate / bench
# ---------------------------------------------------------------------------

def cmd_generate(args) -> int:
    """One-shot local generation (ids in, ids/text out)."""
    import numpy as np

    _export_kv_tier_env(args)
    if getattr(args, "no_spec_adaptive", False):
        print("--no-spec-adaptive requires serve --batch-slots with "
              "--draft-model or --prompt-lookup", file=sys.stderr)
        return 1
    tokenizer = _load_tokenizer(args.tokenizer)
    if args.prompt_ids:
        ids = np.asarray([[int(t) for t in args.prompt_ids.split(",")]],
                         dtype=np.int32)
    elif args.prompt is not None:
        if tokenizer is None:
            print("--prompt requires --tokenizer", file=sys.stderr)
            return 1
        ids = np.asarray([tokenizer.encode(args.prompt)], dtype=np.int32)
    else:
        print("need --prompt-ids or --prompt", file=sys.stderr)
        return 1

    stats = None
    if getattr(args, "draft_model", "") and getattr(args, "prompt_lookup",
                                                    False):
        print("choose one of --draft-model / --prompt-lookup",
              file=sys.stderr)
        return 1
    if getattr(args, "sp", 1) > 1:
        # long-context sequence parallelism: the prompt is sharded over
        # the sp mesh axis, prefill runs ring attention (or Ulysses
        # all-to-all), and the KV cache stays sequence-sharded for the
        # whole generation (parallel/sequence.py, parallel/ulysses.py)
        if (getattr(args, "draft_model", "")
                or getattr(args, "prompt_lookup", False)
                or getattr(args, "tp", 1) > 1):
            print("--sp is exclusive with --draft-model/--prompt-lookup/"
                  "--tp", file=sys.stderr)
            return 1
        return _generate_sp(args, ids, tokenizer)
    if getattr(args, "prompt_lookup", False):
        # draft-free speculation: n-gram lookup over the context proposes,
        # the target verifies (runtime/prompt_lookup.py)
        pld = _build_prompt_lookup_engine(args)
        res, stats = pld.generate(ids, args.max_new_tokens, seed=args.seed)
    elif getattr(args, "draft_model", ""):
        # speculative decoding: the draft model proposes, the target
        # verifies (runtime/speculative.py); shares every engine flag
        spec = _build_spec_engine(args)
        res, stats = spec.generate(ids, args.max_new_tokens, seed=args.seed)
    else:
        _, engine = _build_engine(args)
        res = engine.generate(ids, args.max_new_tokens, seed=args.seed)
    out = {"tokens": res.tokens.tolist(),
           "tokens_per_second": res.tokens_per_second}
    if stats is not None:
        from .runtime.speculative import stats_json
        out["speculative"] = stats_json(stats, args.num_draft)
    if tokenizer is not None:
        out["text"] = [tokenizer.decode(r) for r in res.tokens.tolist()]
    print(json.dumps(out))
    return 0


def _generate_sp(args, ids, tokenizer) -> int:
    """``generate --sp N``: one-shot long-context generation over a local
    sequence-parallel mesh.  ``--sp-strategy ring`` shards the KV cache by
    sequence (ring-attention prefill, log-sum-exp decode reduction);
    ``ulysses`` re-shards by head via all_to_all.  The prompt length must
    be a multiple of N (sharding is by contiguous chunk; pad or trim
    client-side — silent padding would change what the model attends)."""
    import time as _time

    import jax
    import numpy as np

    from .models.registry import get_model_config
    from .parallel.mesh import local_sp_mesh

    unsupported = _sp_unsupported_flags(args)
    if unsupported:
        # the sp generate fns own their attention/cache strategy and have
        # no eos/chunk plumbing — reject loudly rather than silently
        # ignoring the flags
        print(f"{'/'.join(unsupported)} not supported with --sp",
              file=sys.stderr)
        return 1
    from .parallel.sequence import validate_sp_prompt

    cfg = get_model_config(args.model)
    mesh = local_sp_mesh(args.sp)   # call site guards args.sp > 1
    # the generate fns re-validate at call time; running the shared rule
    # HERE fails fast before a multi-GB checkpoint load (its ValueError
    # renders as the CLI's one-line error like every other config error)
    validate_sp_prompt(ids.shape[1], args.sp, args.max_seq,
                       args.max_new_tokens)
    sampling = _sampling_from_args(args)
    kv_dtype = getattr(args, "kv_cache_dtype", None) or None
    if args.sp_strategy == "ring":
        from .parallel.sequence import make_sp_generate_fn
        gen = make_sp_generate_fn(cfg, mesh, max_seq=args.max_seq,
                                  num_new_tokens=args.max_new_tokens,
                                  sampling=sampling,
                                  kv_cache_dtype=kv_dtype)
    else:
        from .parallel.ulysses import make_ulysses_generate_fn
        gen = make_ulysses_generate_fn(cfg, mesh, max_seq=args.max_seq,
                                       num_new_tokens=args.max_new_tokens,
                                       sampling=sampling,
                                       kv_cache_dtype=kv_dtype)
    params = _load_full_params(args, cfg)
    t0 = _time.perf_counter()
    with mesh:
        toks = np.asarray(gen(params, np.asarray(ids),
                              jax.random.PRNGKey(args.seed)))
    dt = _time.perf_counter() - t0
    # like the plain generate path, the one-shot timing includes compile
    out = {"tokens": toks.tolist(),
           "tokens_per_second": toks.size / dt,
           "sp": args.sp, "sp_strategy": args.sp_strategy}
    if tokenizer is not None:
        out["text"] = [tokenizer.decode(r) for r in toks.tolist()]
    print(json.dumps(out))
    return 0


def cmd_classify(args) -> int:
    """Dataset classification accuracy run (the reference's classification
    task: ``Dataset.java:20-44`` CSV in, accuracy out,
    ``BackgroundService.java:233-245``).  Rows are ``text,label``; with
    ``--tokenizer`` the text is encoded, otherwise it must be
    space-separated token ids."""
    import numpy as np

    from .tasks import evaluate_classifier, load_csv_dataset

    ds = load_csv_dataset(args.dataset)
    tokenizer = _load_tokenizer(args.tokenizer)
    prompts = []
    for text in ds.texts:
        if tokenizer is not None:
            ids = tokenizer.encode(text)
        else:
            try:
                ids = [int(t) for t in text.split()]
            except ValueError:
                print("without --tokenizer, dataset text must be "
                      "space-separated token ids", file=sys.stderr)
                return 1
        prompts.append(np.asarray([ids], dtype=np.int32))

    label_ids = [int(t) for t in args.label_token_ids.split(",")]
    if len(label_ids) != len(ds.label_names):
        print(f"--label-token-ids has {len(label_ids)} entries but the "
              f"dataset has {len(ds.label_names)} classes "
              f"({ds.label_names})", file=sys.stderr)
        return 1

    _, engine = _build_engine(args)
    result = evaluate_classifier(
        lambda batch: engine.classify(batch, label_ids),
        prompts, ds.labels, batch_size=args.batch)
    result["label_names"] = ds.label_names
    print(json.dumps(result))
    return 0


def cmd_bench(args) -> int:
    """Engine decode timing of one model on the local device (a user
    role of the reference, PARITY.md row 6; the repo's yardstick is
    ``benchmark/run.py``, not this).

    With ``--prompt-lookup`` or ``--draft-model``, ALSO times the
    speculative engine on the same workload and reports the speedup with
    acceptance stats — how speculation is evaluated on real weights."""
    import numpy as np

    want_pld = bool(getattr(args, "prompt_lookup", False))
    want_draft = bool(getattr(args, "draft_model", ""))
    if want_pld and want_draft:
        print("choose one of --draft-model / --prompt-lookup",
              file=sys.stderr)
        return 1

    spec = None
    if want_pld or want_draft:
        # build the speculative engine FIRST and reuse its target weights
        # for the baseline — loading a large checkpoint twice would hold
        # two copies in device memory (and can OOM exactly the models
        # this comparison is for)
        spec = (_build_prompt_lookup_engine(args) if want_pld
                else _build_spec_engine(args))
        from .runtime import InferenceEngine
        engine = InferenceEngine(
            spec.cfg, spec.params, max_seq=args.max_seq,
            sampling=_sampling_from_args(args),
            attn_backend=args.attn_backend, mesh=spec.mesh)
    else:
        _, engine = _build_engine(args)

    prompt = np.arange(args.batch * args.prompt_len).reshape(
        args.batch, args.prompt_len) % 1000
    engine.generate(prompt, args.max_new_tokens, seed=0)       # compile
    res = engine.generate(prompt, args.max_new_tokens, seed=0)
    out = {
        "metric": f"decode tokens/sec ({args.model}, batch={args.batch}, "
                  f"prompt={args.prompt_len}, new={args.max_new_tokens})",
        "value": round(res.tokens_per_second, 2),
        "unit": "tokens/sec",
    }
    if spec is not None:
        from .runtime.speculative import stats_json
        spec.generate(prompt, args.max_new_tokens, seed=0)     # compile
        sres, stats = spec.generate(prompt, args.max_new_tokens, seed=0)
        out["speculative"] = dict(
            stats_json(stats, args.num_draft),
            tokens_per_sec=round(sres.tokens_per_second, 2),
            speedup=round(sres.tokens_per_second
                          / res.tokens_per_second, 3))
    print(json.dumps(out))
    return 0


# ---------------------------------------------------------------------------

def _add_engine_args(ap):
    ap.add_argument("--model", required=True)
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--max-new-tokens", type=int, default=128)
    ap.add_argument("--weights-seed", type=int, default=0)
    ap.add_argument("--checkpoint", default="",
                    help="local safetensors dir (else random init)")
    ap.add_argument("--tokenizer", default="",
                    help="tokenizer.json path for text in/out")
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--top-k", type=int, default=7)
    ap.add_argument("--min-p", type=float, default=0.0,
                    help="min-p filter: keep tokens with probability >= "
                         "min_p * max_prob on the temperature-scaled "
                         "distribution (0 disables; composes with top-k "
                         "and top-p)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-backend", default="auto",
                    choices=["auto", "flash", "jnp"])
    ap.add_argument("--eos-id", type=int, default=None,
                    help="end-of-sequence token id: finished rows pad "
                         "with it and generation stops early once every "
                         "row emitted it")
    ap.add_argument("--kv-cache-dtype", default="",
                    help="reduced-precision KV cache storage, e.g. "
                         "float8_e4m3fn (half the cache bytes; small "
                         "accuracy cost)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="process prompts in fixed chunks of N tokens "
                         "(bounds prefill activation memory on long "
                         "prompts; with --batch-slots it also bounds the "
                         "decode stall a long admission imposes on "
                         "in-flight rows; 0 = whole-prompt prefill)")
    ap.add_argument("--stream-block", type=int, default=None,
                    help="fuse N decode steps per streaming dispatch "
                         "(docs/DESIGN.md §13): one host dispatch "
                         "per N tokens with on-device eos/stop matching "
                         "and early exit; output is bit-identical to "
                         "the per-token path; default DWT_STREAM_BLOCK "
                         "or 1")
    ap.add_argument("--kv-cache-blocks", type=int, default=None,
                    help="block-level KV prefix cache (runtime/kvcache): "
                         "host block-pool size in blocks; prompts sharing "
                         "whole leading blocks with earlier prefills skip "
                         "that prefill (radix-tree partial matches, exact "
                         "reuse).  Default: DWT_KVCACHE_BLOCKS, else on "
                         "(64) for --batch-slots and off (0) for the "
                         "single-request engines; 0 disables")
    ap.add_argument("--kv-block-tokens", type=int, default=None,
                    help="tokens per KV cache block (match granularity "
                         "AND minimum reusable prefix; default "
                         "DWT_KVCACHE_BLOCK_TOKENS, else 16)")
    ap.add_argument("--kv-host-tier-bytes", type=int, default=None,
                    help="tiered KV (docs/DESIGN.md §21): byte budget "
                         "of the host-RAM ring that catches KV blocks "
                         "LRU-evicted from the device page pool; a "
                         "radix miss whose prefix sits demoted promotes "
                         "it back for one h2d adopt instead of "
                         "re-prefilling.  Default DWT_KV_HOST_TIER_"
                         "BYTES, else 0 (off)")
    ap.add_argument("--kv-disk-tier-path", default=None,
                    help="optional mmap'd disk segment BELOW the host "
                         "ring: host-budget overflow spills here "
                         "(oldest first) instead of dropping; requires "
                         "--kv-host-tier-bytes > 0 and "
                         "--kv-disk-tier-bytes.  Default "
                         "DWT_KV_DISK_TIER_PATH")
    ap.add_argument("--kv-disk-tier-bytes", type=int, default=None,
                    help="byte budget of the disk segment (0 = no disk "
                         "tier; default DWT_KV_DISK_TIER_BYTES)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=["bf16", "int8", "int4"],
                    help="KV page WIDTH for the paged pool "
                         "(docs/DESIGN.md §17): bf16 stores full-width "
                         "pages (the default); int8 / packed int4 "
                         "quantize each page at write time with a "
                         "per-token scale sidecar riding the block "
                         "table — 2x / 4x the admissible batch at a "
                         "fixed HBM budget, small pinned accuracy "
                         "cost.  Default DWT_KV_DTYPE, else bf16; "
                         "mutually exclusive with --kv-cache-dtype")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor parallelism over the first N local "
                         "devices (Megatron-sliced weights, kv-head-"
                         "sharded cache; single-node serve/generate only)")


def _add_sp_args(p) -> None:
    """Sequence/context-parallelism flags, shared by generate and serve."""
    p.add_argument("--sp", type=int, default=1,
                   help="sequence/context parallelism over the first N "
                        "local devices for LONG prompts: the prompt "
                        "shards by contiguous chunk, prefill runs ring "
                        "attention (or Ulysses), the KV cache stays "
                        "sharded for the whole generation; prompt length "
                        "must divide by N")
    p.add_argument("--sp-strategy", default="ring",
                   choices=["ring", "ulysses"],
                   help="ring = sequence-sharded cache + ring-attention "
                        "prefill; ulysses = all_to_all to head-sharded "
                        "attention (needs heads divisible by N)")
    p.add_argument("--sp-queue-depth", type=int, default=None,
                   help="serve --sp: max requests allowed to WAIT behind "
                        "the one running before arrivals get 429 + "
                        "Retry-After (the sp mesh serializes requests); "
                        "default DWT_SP_QUEUE_DEPTH or 8, 0 = unbounded")


def _sp_unsupported_flags(args, allow_eos: bool = False) -> list:
    """Engine flags the sp paths have no plumbing for — one rule shared
    by ``generate --sp`` and ``serve --sp`` so the two surfaces cannot
    drift.  Rejected loudly rather than silently ignored.  ``serve``
    passes ``allow_eos=True``: its backend honors eos via the step-split
    stream programs; the one-shot generate fns are fused with a baked
    trip count and cannot."""
    return [flag for flag, on in [
        ("--eos-id", not allow_eos
         and getattr(args, "eos_id", None) is not None),
        ("--prefill-chunk", bool(getattr(args, "prefill_chunk", 0))),
        ("--stream-block",
         getattr(args, "stream_block", None) is not None),
        ("--kv-cache-blocks", _kvcache_flags_set(args)),
        ("--attn-backend", args.attn_backend != "auto")] if on]


def _add_draft_args(p) -> None:
    """Speculative-decoding flags, shared by generate and serve."""
    p.add_argument("--draft-model", default="",
                   help="speculative decoding: draft model name (must "
                        "share the target's vocab)")
    p.add_argument("--draft-checkpoint", default="",
                   help="checkpoint for the draft model weights")
    p.add_argument("--num-draft", type=int, default=4,
                   help="draft tokens proposed per verify round")
    p.add_argument("--prompt-lookup", action="store_true",
                   help="draft-FREE speculation: n-gram lookup over the "
                        "context proposes, the target verifies")
    p.add_argument("--no-spec-adaptive", action="store_true",
                   help="pin K_row = --num-draft in the mixed dispatch "
                        "instead of adapting per-row draft length to "
                        "measured acceptance (serve --batch-slots only)")


def configure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache — the ONE site that
    does, passed by every entry point before a backend exists.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
    set in code and the directory is returned for the record.  Unset:
    ``<checkout>/.jax_cache``, derived from this package's own location
    — never from a temp name, a pid or a time, because the directory is
    part of how a later process finds what an earlier one compiled."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="distributed_inference_demo_tpu",
        description="TPU-native distributed LLM inference framework")
    # multi-host SPMD: join JAX's distributed runtime before any command
    # touches a backend; afterwards jax.devices() spans every host and the
    # parallel/ meshes run cross-host with collectives on ICI/DCN
    ap.add_argument("--jax-coordinator", default="",
                    help="host:port of process 0, enables multi-host JAX")
    ap.add_argument("--jax-num-processes", type=int, default=1)
    ap.add_argument("--jax-process-id", type=int, default=0)
    sub = ap.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("serve", help="HTTP inference server")
    _add_engine_args(s)
    s.add_argument("--http-host", default="127.0.0.1")
    s.add_argument("--http-port", type=int, default=5000)
    s.add_argument("--chain", default="",
                   help="pipeline mode: comma list of workerid@host:port")
    s.add_argument("--device-id", default="header")
    s.add_argument("--bind-host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=0,
                   help="data-plane port (pipeline mode)")
    s.add_argument("--step-timeout", type=float, default=120.0)
    s.add_argument("--pool-size", type=int, default=1,
                   help="with --chain: dynamic batching — concurrent HTTP "
                        "requests group into windows of up to N in-flight "
                        "rids interleaving through the pipeline stages "
                        "(1 = serialized requests)")
    s.add_argument("--batch-slots", type=int, default=0,
                   help="continuous batching with N slots: concurrent "
                        "requests join the running decode batch between "
                        "steps (single-node mode only)")
    s.add_argument("--decode-block", type=int, default=1,
                   help="with --batch-slots: fuse N decode steps (or N "
                        "draft/verify rounds under --draft-model/"
                        "--prompt-lookup) per dispatch when no admission "
                        "could land anyway (one host sync per block; "
                        "admission latency <= N steps)")
    s.add_argument("--mixed-token-budget", type=int, default=0,
                   help="with --batch-slots and --prefill-chunk: pack "
                        "prefill chunk tokens from admitting prompts "
                        "into the SAME dispatch as the fused decode "
                        "block, up to N tokens total per step "
                        "(docs/DESIGN.md §19; decode fusion survives "
                        "admission and output stays bit-identical to "
                        "the serialized interleave; default "
                        "DWT_MIXED_TOKEN_BUDGET or 0 = serialized)")
    s.add_argument("--vision", action="store_true",
                   help="LLaVA-style multimodal serving: /generate takes "
                        "an optional 'image' field ([H][W][C] floats); "
                        "text-only requests serve unchanged")
    s.add_argument("--vision-preset", default="small",
                   choices=["small", "llava15", "clip-test"],
                   help="ViT tower geometry: small = 224px/6 layers, "
                        "llava15 = CLIP-ViT-L/14-336 faithful (class "
                        "token, pre-layernorm, quick_gelu, penultimate "
                        "feature select), clip-test = tiny faithful "
                        "tower for tests")
    s.add_argument("--vision-checkpoint", default="",
                   help="safetensors dir with HF CLIP/LLaVA vision tower "
                        "weights (vision_model.* names; LLaVA's "
                        "multi_modal_projector loads too when present); "
                        "empty = seeded random init")
    s.add_argument("--run-log", default="",
                   help="append structured JSONL run-log events "
                        "(serve start + per-request engine summaries) "
                        "to this path (telemetry/runlog)")
    s.add_argument("--postmortem-dir", default="",
                   help="write postmortem bundles (flight-recorder ring "
                        "+ metrics + trace + run-log tail) here on "
                        "anomaly/stall/crash; equivalent to "
                        "DWT_POSTMORTEM_DIR (docs/DESIGN.md §8)")
    s.add_argument("--admission-queue-depth", type=int, default=0,
                   help="with --batch-slots: shed load — when this many "
                        "requests are already waiting for a slot, "
                        "/generate answers 503 + Retry-After instead of "
                        "queueing unboundedly (0 = unbounded; env "
                        "DWT_MAX_QUEUE_DEPTH)")
    s.add_argument("--request-timeout", type=float, default=0.0,
                   help="per-request deadline in seconds for blocking "
                        "/generate: on expiry the request is CANCELLED "
                        "(its slot freed) and the client gets 504 "
                        "instead of a hang (0 = no deadline)")
    s.add_argument("--fault-plan", default="",
                   help="CHAOS TESTING ONLY: JSON fault-plan spec (path "
                        "or inline) injected into the data-plane "
                        "transport; requires --chaos and --chain "
                        "(docs/DESIGN.md §12; env DWT_FAULT_PLAN)")
    s.add_argument("--chaos", action="store_true",
                   help="explicitly acknowledge fault injection; "
                        "--fault-plan/DWT_FAULT_PLAN are rejected "
                        "without it")
    _add_sp_args(s)
    _add_draft_args(s)
    s.set_defaults(fn=cmd_serve)

    gw = sub.add_parser("gateway", help="replicated serving gateway: "
                        "prefix-aware routing over N serve replicas")
    gw.add_argument("--replicas", default="",
                    help="comma list of replica host:port (each a running "
                         "'serve' process); required except with "
                         "--drain/--undrain")
    gw.add_argument("--drain", default="",
                    help="client mode: mark REPLICA (host:port) draining "
                         "on the running gateway at --http-host/--http-"
                         "port — new requests stop routing to it while "
                         "in-flight streams finish (docs/DESIGN.md §18)")
    gw.add_argument("--undrain", default="",
                    help="client mode: clear REPLICA's draining flag")
    gw.add_argument("--http-host", default="127.0.0.1")
    gw.add_argument("--http-port", type=int, default=5080)
    gw.add_argument("--health-interval", type=float, default=1.0,
                    help="seconds between /stats health probes")
    gw.add_argument("--probe-timeout", type=float, default=2.0)
    gw.add_argument("--evict-sustain", type=int, default=3,
                    help="consecutive failures before a replica is "
                         "evicted from routing")
    gw.add_argument("--readmit-cooldown", type=float, default=5.0,
                    help="seconds a recovered replica must wait before "
                         "readmission")
    gw.add_argument("--min-prefix-tokens", type=int, default=16,
                    help="shortest prefix match that beats the hash "
                         "fallback")
    gw.add_argument("--route-block-tokens", type=int, default=16,
                    help="prefix-index granularity in tokens (match the "
                         "replicas' --kv-block-tokens)")
    gw.add_argument("--load-factor", type=float, default=2.0,
                    help="hashed picks above load_factor x (1 + fleet "
                         "mean load) are skipped down the rendezvous "
                         "order")
    gw.add_argument("--retry-limit", type=int, default=1,
                    help="alternate replicas tried when the routed one "
                         "dies before first token")
    gw.add_argument("--resume-limit", type=int, default=1,
                    help="mid-stream failover attempts: a replica dying "
                         "AFTER first token is resumed bit-identically "
                         "on a survivor this many times before the "
                         "error-line fallback (0 = disable)")
    gw.add_argument("--proxy-timeout", type=float, default=0.0,
                    help="per-socket replica timeout in seconds "
                         "(0 = none)")
    gw.set_defaults(fn=cmd_gateway)

    sv = sub.add_parser("server", help="integrated root server: collect, "
                        "profile, plan, distribute, serve")
    _add_engine_args(sv)
    sv.add_argument("--num-workers", type=int, default=1)
    sv.add_argument("--pool-size", type=int, default=1,
                    help="dynamic batching at the composed server's HTTP "
                         "surface: concurrent requests group into windows "
                         "of up to N in-flight pipeline requests")
    sv.add_argument("--bind-host", default="127.0.0.1")
    sv.add_argument("--http-host", default="127.0.0.1")
    sv.add_argument("--http-port", type=int, default=0)
    sv.add_argument("--collect-window", type=float, default=10.0,
                    help="quiet window closing device collection (ref 10s)")
    sv.add_argument("--collect-timeout", type=float, default=120.0)
    sv.add_argument("--monitor-timeout", type=float, default=60.0)
    sv.add_argument("--step-timeout", type=float, default=120.0)
    sv.set_defaults(fn=cmd_server)

    w = sub.add_parser("worker", help="pipeline stage worker",
                       add_help=False)
    w.add_argument("--elastic", action="store_true")
    w.add_argument("--auto", action="store_true",
                   help="receive role/range/weights from a `server` app")
    w.set_defaults(fn=cmd_worker)

    p = sub.add_parser("plan", help="partition planning")
    p.add_argument("--model", required=True)
    p.add_argument("--devices", help="JSON file: list of DeviceProfile")
    p.add_argument("--ctx", type=int, default=1024)
    p.add_argument("--round-robin", action="store_true",
                   help="reference-parity round robin instead of the "
                        "cost-model DP")
    p.add_argument("--save", default="")
    p.add_argument("--load", default="")
    p.set_defaults(fn=cmd_plan)

    c = sub.add_parser("chat", help="streaming chat REPL against a "
                       "serve/server HTTP endpoint")
    c.add_argument("--url", default="http://127.0.0.1:5000")
    c.add_argument("--max-new-tokens", type=int, default=128)
    c.add_argument("--stop", action="append", default=None,
                   help="stop sequence (repeatable); needs a server-side "
                        "tokenizer — generation ends at the earliest "
                        "match, which is not rendered")
    c.add_argument("--tokenizer", default="",
                   help="local tokenizer.json for encode/decode (else the "
                        "server's tokenizer handles text)")
    c.add_argument("--ids", action="store_true",
                   help="input lines are comma-separated token ids")
    c.add_argument("--template", default="User: {msg}. Response:",
                   help="prompt template (reference "
                        "BackgroundService.java:211)")
    c.add_argument("--seed", type=int, default=0)
    c.set_defaults(fn=cmd_chat)

    g = sub.add_parser("generate", help="one-shot local generation")
    _add_engine_args(g)
    g.add_argument("--prompt-ids", default="")
    g.add_argument("--prompt", default=None)
    _add_sp_args(g)
    _add_draft_args(g)
    g.set_defaults(fn=cmd_generate)

    b = sub.add_parser("bench", help="decode throughput benchmark")
    _add_engine_args(b)
    b.add_argument("--batch", type=int, default=8)
    b.add_argument("--prompt-len", type=int, default=64)
    _add_draft_args(b)
    b.set_defaults(fn=cmd_bench)

    cl = sub.add_parser("classify", help="CSV dataset classification "
                        "accuracy run")
    _add_engine_args(cl)
    cl.add_argument("--dataset", required=True,
                    help="CSV file of text,label rows (Dataset.java:20-44)")
    cl.add_argument("--label-token-ids", required=True,
                    help="comma list: one verbalizer token id per class, "
                         "in dataset label-name order")
    cl.add_argument("--batch", type=int, default=8)
    cl.set_defaults(fn=cmd_classify)

    args, rest = ap.parse_known_args(argv)
    args.rest = rest
    if rest and args.cmd != "worker":
        # `worker` hands what is left to its role's own parser; anywhere
        # else a flag nobody knows is a mistake, not something to drop
        ap.error("unrecognized arguments: " + " ".join(rest))
    if args.cmd == "plan" and not (args.devices or args.load):
        ap.error("plan needs --devices or --load")
    if args.cmd not in ("gateway", "chat", "plan"):
        # the commands that compile; the gateway, the chat client and
        # the planner never touch a backend and stay off jax.config
        configure_compile_cache()
    if args.jax_coordinator:
        from .parallel.mesh import init_multihost
        init_multihost(args.jax_coordinator, args.jax_num_processes,
                       args.jax_process_id)
    elif args.jax_num_processes != 1 or args.jax_process_id != 0:
        # a forgotten coordinator must not silently run single-host
        ap.error("--jax-num-processes/--jax-process-id require "
                 "--jax-coordinator")
    try:
        return args.fn(args)
    except ValueError as e:
        # configuration errors raised below the flag layer (e.g. a tp
        # mesh rejecting kv_cache_dtype, or tp > local devices) render as
        # one stderr line, matching the CLI's explicit flag guards.
        # DIDEMO_DEBUG=1 re-raises with the full traceback so a genuine
        # bug surfacing as ValueError isn't flattened to one line.
        import os
        if os.environ.get("DIDEMO_DEBUG") == "1":
            raise
        print(f"error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
