#!/usr/bin/env python3
"""Compile a model's ``mixed_step`` for a TPU v5e that is not there, and
say what the compiler reports: device memory (arguments, temporaries),
the attention path each chunk shape took, how the pool reached it, the
Pallas custom calls, and every op of the optimized HLO whose result is at
least as large as one layer's plane of the page pool.

    python tools/aot_mixed_step.py --model olmoe-1b-7b-int8 \\
        --batch-slots 32 --prefill-chunk 256 --decode-block 4 \\
        --mixed-token-budget 640 --max-seq 4096 --kv-block-tokens 128 \\
        --kv-cache-blocks 192 208 224

``--model`` is a registry name or a benchmark configuration's
(``benchmark/configs/<name>.json``: ``tools/bench_config.py``).

libtpu compiles for a described topology (``v5e:2x2``, one of its
devices) without a chip; parameters and the page pool are shapes only, so
nothing model-sized is allocated.  It proves compilation and sizes a pool
before any chip call (PERF.md section 4); times need the chip.  One
compile a variant of the program a ``--kv-cache-blocks`` value: a
dispatch runs a slab of as many segments as it packed, 1 to budget //
chunk of them, and the decode loop, and one that packed none the decode
loop alone (largest first); a refusal (out of memory) is printed, not
raised.

Reading the large ops ("no pool copy" without a chip).  The pool is
addressed in place (``ops.stacked.LayerOf``): on the chip the KV write is
the Pallas call ``kv_page_write`` (named under "pallas calls"; its result
is a tuple aliased to the pool, and tuples, like the layer scan's
``while``, are not listed; a latent-attention model's ONE pool comes back
as the array itself and IS listed, once a ``kv_page_write``, aliased all
the same: read the temporaries), so a sound program lists NOTHING of the pool's
or of a plane's shape, "pool" says ``kernel write`` for every chunk shape,
and the temporaries stay far under the pool's size.  What must not be
there: an op of a PLANE's shape ``[N, H, bt, D]`` (a layer sliced out of
the pool: ``dynamic-slice`` / ``dynamic-update-slice`` fusions, once a
layer call; "pool" then says ``plane``, which is what int8 / int4 pages,
heads under 128 and a ``--prefill-chunk`` that is not a multiple of 16
take: ``ops.paged_attention.route_pool``), and a ``copy`` of the pool's
shape (the compiler giving a consumer another layout; it shows as
temporaries of about the pool's size).  Ops as large that are not the
pool's (a weight matrix widened, logits) are listed too, by shape.

"weight leaves copied" names every instruction whose result has the shape
and dtype of a leaf of ``params.layers``, the stack's ``L`` included.
A copied stack means a consumer wants the weights in another layout than
they are stored in: the whole stack is rewritten every execution (its size
in temporaries, its bytes twice through HBM) and, as a rule, each layer
call then writes its own matrix out before the matmul reads it.  That is
what a head reshape folded into the q / k / v dot did until PR 42 (a
convolution with ``window={size=<heads>}``).  ``on chip`` marks a result in
the compiler's fast memory (``S(1)`` in its layout): a prefetch of a small
stack, no HBM temporary and no other layout.  A sound program lists none.
"""

from __future__ import annotations

import argparse
import itertools
import math
import re
import sys
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

GIB = float(1 << 30)


def compile_mixed_step(model: str, blocks: int, args, segments=None):
    """``(compiled, engine)`` of ``mixed_step`` at ``blocks`` pool pages:
    the variant of a dispatch that packed ``segments`` prefill segments
    (default: all the budget holds; 0: the decode loop alone)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from bench_config import model_config_for
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    from distributed_inference_demo_tpu.ops import quant
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    sharding = jax.sharding.SingleDeviceSharding(topo.devices[0])
    cfg = model_config_for(model)
    # a name without a quant suffix is served at its own dtype
    # (quantize=True alone would make int8 leaves of a bf16 model)
    params = jax.eval_shape(
        lambda: init_full_params(jax.random.PRNGKey(0), cfg,
                                 quantize=cfg.quantization != "none"))
    pool = quant.alloc_kv_pool

    def abstract_pool(*a, **k):
        return jax.eval_shape(lambda: pool(*a, **k))

    # shapes only: the engine launches nothing before it is ready
    with mock.patch.object(quant, "alloc_kv_pool", abstract_pool), \
            mock.patch.object(ContinuousBatchingEngine,
                              "_warm_mixed_variants", lambda self: None):
        eng = ContinuousBatchingEngine(
            cfg, params, max_seq=args.max_seq, max_batch=args.batch_slots,
            sampling=SamplingParams(temperature=0.0),
            prefill_chunk=args.prefill_chunk,
            decode_block=args.decode_block,
            mixed_token_budget=args.mixed_token_budget,
            kv_cache_blocks=blocks, kv_block_tokens=args.kv_block_tokens)
    try:
        B, W = args.batch_slots, eng._table_cols  # a table a pool
        r = eng._mixed_seg_cap if segments is None else segments

        def S(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        def on_chip(tree):
            return jax.tree.map(lambda x: S(x.shape, x.dtype), tree)

        i32 = jnp.int32
        seg = on_chip(eng._slab_of(eng._blank_segments(), r))
        call = (on_chip(params), on_chip(eng._pk), on_chip(eng._pv),
                seg if r else None, S((B, W), i32), S((B,), i32),
                S((B,), i32), S((B,), jnp.bool_), S((2,), jnp.uint32),
                S((), i32), S((B,), i32), args.decode_block)
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            compiled = eng._mixed_step.inner.lower(*call).compile()
        return compiled, eng
    finally:
        eng.close()


_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "bf16": 2,
             "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4,
             "f64": 8, "s64": 8, "u64": 8}
# ops that name a buffer and make none
_NO_BUFFER = ("parameter", "get-tuple-element", "bitcast", "tuple",
              "while", "conditional", "call")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.-]+) = (\w+)\[([\d,]*)\]([^ ]*) ([\w-]+)\(")


def _buffers(hlo_text: str):
    """``(name, opcode, shape, bytes, layout)`` of every instruction of an
    optimized HLO module that makes an array, outside fused computations
    (what a fusion computes inside makes no buffer)."""
    fused = set(re.findall(r"calls=%?([\w.-]+)", hlo_text))
    skip = False
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$", line)
        if head:
            skip = head.group(1) in fused
            continue
        m = None if skip else _INSTRUCTION.match(line)
        if not m or m.group(5) in _NO_BUFFER or m.group(2) not in _ITEMSIZE:
            continue
        name, dtype, dims, layout, opcode = m.groups()
        shape = [int(d) for d in dims.split(",") if d]
        yield (name, opcode, f"{dtype}{shape}",
               _ITEMSIZE[dtype] * math.prod(shape), layout)


def large_ops(hlo_text: str, min_bytes: int) -> list:
    """``[(name, opcode, shape, bytes)]`` of the instructions of an
    optimized HLO module whose array result is at least ``min_bytes``,
    outside fused computations, largest first."""
    return sorted((b[:4] for b in _buffers(hlo_text) if b[3] >= min_bytes),
                  key=lambda r: -r[3])


_HLO_DTYPE = {"int8": "s8", "uint8": "u8", "bfloat16": "bf16",
              "float16": "f16", "float32": "f32", "int32": "s32"}


def copied_weight_leaves(hlo_text: str, layers) -> list:
    """``[(name, opcode, shape, bytes, on_chip)]`` of the instructions
    whose result has the shape and dtype of a leaf of ``layers`` (the
    stacked ``params.layers``), largest first; ``on_chip`` where the
    result lies in the compiler's fast memory and not in HBM."""
    import jax
    stacks = {f"{_HLO_DTYPE.get(a.dtype.name, a.dtype.name)}{list(a.shape)}"
              for a in jax.tree.leaves(layers)}
    return sorted((b[:4] + ("S(1)" in b[4],) for b in _buffers(hlo_text)
                   if b[2] in stacks), key=lambda r: -r[3])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", required=True)
    ap.add_argument("--batch-slots", type=int, default=32)
    ap.add_argument("--prefill-chunk", type=int, default=256)
    ap.add_argument("--decode-block", type=int, default=4)
    ap.add_argument("--mixed-token-budget", type=int, default=640)
    ap.add_argument("--max-seq", type=int, default=4096)
    ap.add_argument("--kv-block-tokens", type=int, default=128)
    ap.add_argument("--kv-cache-blocks", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    n_seg = max(1, args.mixed_token_budget // args.prefill_chunk)
    for blocks, r in itertools.product(args.kv_cache_blocks,
                                       range(n_seg, -1, -1)):
        which = (f"blocks={blocks} " + (
            f"slab of {r} segment{'s' * (r > 1)} + decode loop" if r
            else "decode loop alone"))
        try:
            compiled, eng = compile_mixed_step(args.model, blocks, args, r)
        except Exception as e:              # the compiler's refusal
            msg = " ".join(str(e).split())
            print(f"{which}: REFUSED {type(e).__name__}: {msg[:600]}",
                  flush=True)
            continue
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        calls = sorted(set(re.findall(
            r"%([\w.-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call", hlo)))
        total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        print(f"{which}: arguments "
              f"{ma.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{ma.temp_size_in_bytes / GIB:.2f} GiB, outputs "
              f"{ma.output_size_in_bytes / GIB:.2f} GiB, aliased "
              f"{ma.alias_size_in_bytes / GIB:.2f} GiB: "
              f"{total / GIB:.2f} GiB; paths "
              f"{eng.attn_paths.snapshot()}; pool "
              f"{eng.attn_paths.addressing()}; pallas calls {calls}",
              flush=True)
        # one pool a kind of block (a model of one kind: one pool); the
        # smallest plane bounds the list below
        leaves = jax.tree.leaves(eng._pk)
        plane = min(leaf.dtype.itemsize * math.prod(leaf.shape[1:])
                    for leaf in leaves)
        for leaf in leaves:
            size = leaf.dtype.itemsize * math.prod(leaf.shape)
            print(f"  pool leaf {leaf.dtype}{list(leaf.shape)} "
                  f"{size / GIB:.2f} GiB, one plane "
                  f"{size / leaf.shape[0] / (1 << 20):.1f} MiB", flush=True)
        print("  ops with a result of at least a plane:", flush=True)
        for name, opcode, shape, size in large_ops(hlo, plane):
            print(f"    {name}  {opcode}  {shape}  "
                  f"{size / (1 << 20):.1f} MiB", flush=True)
        copied = [f"{name} {shape} {size / (1 << 20):.1f} MiB"
                  + " (on chip)" * on_chip for name, _, shape, size, on_chip
                  in copied_weight_leaves(hlo, eng.params.layers)]
        print(f"  weight leaves copied: {'; '.join(copied) or 'none'}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
