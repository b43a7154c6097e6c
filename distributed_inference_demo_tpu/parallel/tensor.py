"""Tensor-parallel stage execution (Megatron layout over the ``tp`` axis).

BASELINE.json config #3: "Llama-3-8B tensor-parallel: attention-head shards
across 8 TPU chips via ICI all-gather".  The forward is
``decoder.stage_forward`` run inside ``jax.shard_map`` with column/row-
sliced weights and explicit psum/all-gather collectives (see
``decoder._layer(tp_axis=...)``); the KV cache lives sharded by kv-head so
each chip only touches its heads' cache lines.
"""

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.base import (KVCache, ModelConfig, StageParams, StageSpec,
                           require_kv_pair, require_one_kind,
                           require_token_rows)
from ..models.decoder import stage_forward
from .sharding import stage_param_spec_tree


def _tp_param_specs(params: StageParams, cfg: ModelConfig) -> StageParams:
    # lm_head is vocab-column-sharded; stage_forward all-gathers the logit
    # shards at the sampling boundary.  embed stays replicated (id gather).
    return stage_param_spec_tree(params, cfg, pp_shard=False, use_tp=True,
                                 vocab_parallel_embed=False)


# head-major cache [layers, batch, nkv, seq, hd]: shard the kv-head axis.
# The spec doubles as a pytree PREFIX: a quantized page pool
# (ops.quant.QuantizedKVPages) hangs data/scale/zero leaves under keys/
# values, all keeping the [L, N, H, bt, ·] axis order with a trailing
# singleton on the sidecars — the one rank-5 spec broadcasts over the
# subtree, so scale tensors shard WITH their pages and no quantized
# variant of this spec exists (docs/DESIGN.md §17).
_CACHE_SPEC = KVCache(keys=P(None, None, "tp", None, None),
                      values=P(None, None, "tp", None, None),
                      length=P())


def tp_cache_sharding(mesh: Mesh) -> KVCache:
    """NamedShardings for a KVCache on the tp mesh (kv-head-sharded) —
    for committing fresh cache buffers to their shards up front.  The
    spec is written as a program hands it back, without the trailing
    ``None``s: a jit's call cache keys on the sharding as written, and a
    fresh buffer then shares the entry of one that a program returned."""
    from jax.sharding import NamedSharding
    heads = NamedSharding(mesh, P(None, None, "tp"))
    return KVCache(keys=heads, values=heads,
                   length=NamedSharding(mesh, _CACHE_SPEC.length))


def validate_tp(cfg: ModelConfig, mesh: Mesh) -> int:
    """Check the config can shard over the mesh's tp axis; returns tp."""
    tp = mesh.shape.get("tp", 1)
    if tp > 1:
        require_kv_pair(cfg, "tensor parallelism (--tp)")
        require_token_rows(cfg, "tensor parallelism (--tp)")
        require_one_kind(cfg, "tensor parallelism (--tp)")
    if tp > 1 and cfg.num_kv_heads % tp:
        raise ValueError(
            f"num_kv_heads={cfg.num_kv_heads} not divisible by tp={tp}")
    return tp


def make_tp_forward(cfg: ModelConfig, spec: StageSpec, mesh: Mesh,
                    params_template: StageParams, attn_impl=None):
    """``fwd(params, inputs, cache, positions, logits_at)`` running
    ``stage_forward`` inside a tp shard_map — the seam every engine builds
    its jits on (runtime/engine.py, speculative.py, prompt_lookup.py,
    batching.py).  Activations/positions/logits are replicated; weights
    and the KV cache stay sharded per this module's specs.

    ``logits_at`` is ``stage_forward``'s: ``None`` for logits at every
    position, else the one position a row whose logits the caller reads
    (replicated, like the inputs).  Each rank gathers those rows before
    its slice of the head, so the vocab-parallel ``all_gather`` moves
    ``[b, 1, V / tp]`` a rank, never ``[b, s, V / tp]``.

    ``attn_impl`` runs INSIDE the shard (per-rank head counts, local
    kv-head cache plane) — e.g. batching's per-slot scatter impl; None
    uses the default insert-and-attend path."""
    validate_tp(cfg, mesh)
    p_specs = _tp_param_specs(params_template, cfg)

    def fwd(p, inputs, cache, positions, logits_at):
        def body(p, i, c, po, at):
            return stage_forward(p, cfg, spec, i, c, po, tp_axis="tp",
                                 attn_impl=attn_impl, logits_at=at)
        # ``logits_at=None`` is an empty pytree: its spec matches nothing
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, P(), _CACHE_SPEC, P(), P()),
            out_specs=(P(), _CACHE_SPEC),
            check_vma=False)(p, inputs, cache, positions, logits_at)

    return fwd


def make_forward_seam(cfg: ModelConfig, spec: StageSpec, mesh,
                      params_template: StageParams, attn_impl=None):
    """(fwd, cache_sharding) for an engine: the tp shard_map seam when
    ``mesh`` has a tp axis > 1, else a plain ``stage_forward`` closure
    with ``cache_sharding=None``.  The one mesh-dispatch rule shared by
    every engine constructor (engine.py, speculative.py,
    prompt_lookup.py, batching.py)."""
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if tp > 1:
        return (make_tp_forward(cfg, spec, mesh, params_template,
                                attn_impl=attn_impl),
                tp_cache_sharding(mesh))

    def fwd(p, inputs, cache, positions, logits_at):
        return stage_forward(p, cfg, spec, inputs, cache, positions,
                             attn_impl=attn_impl, logits_at=logits_at)

    return fwd, None


def make_paged_forward_seam(cfg: ModelConfig, spec: StageSpec, mesh,
                            params_template: StageParams,
                            block_tokens: int, backend: str = "auto",
                            interpret: bool = False, record=None):
    """``(fwd, bind, pool_sharding)`` for a PAGED-cache engine: the
    forward runs ``ops.paged_attention``'s block-table hook over a page
    pool ``[L, N, H, bt, D]`` standing in for the dense cache buffers.

    ``bind(tables, program)`` hands the current dispatch's block tables
    to the hook and names the compiled program being traced — call it
    at the top of the caller's jitted body, before the first ``fwd``.
    Off-mesh, the hook reads the binding by closure (a loop constant of
    the trace).  Under a tp mesh the tables are threaded through
    ``shard_map`` as an explicit replicated argument instead —
    shard_map bodies must not close over traced values — and the pool
    shards by kv head exactly like the dense cache (``_CACHE_SPEC``:
    axis 2 either way), so each chip pages only its own head planes and
    the attention kernels run per shard on ``nkv / tp`` heads.
    ``backend`` / ``interpret`` / ``record`` go to
    ``make_paged_attn_impl`` unchanged, on-mesh and off: the path every
    program took is in ``record``.  The one paged-dispatch rule shared
    by the batching scheduler and the ring stage runtimes.

    ``fwd(..., moe_stats=True)`` (a model with experts) returns
    ``stage_forward``'s third value too, the ``[layers, E]`` rows routed
    to each expert, replicated under a mesh; ``valid`` is
    ``stage_forward``'s (the rows that hold a token) and ``logits_at``
    too (the one position a row that wants logits, or ``None`` for all:
    see :func:`make_tp_forward`), both replicated like the inputs."""
    from ..ops.paged_attention import make_paged_attn_impl
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if tp <= 1:
        if cfg.latent_kv:   # one latent row a token: its own hook
            from ..ops.latent_attention import make_latent_attn_impl
            impl, bind = make_latent_attn_impl(
                cfg.kv_lora_rank, cfg.latent_scale, backend, interpret,
                record)
        else:
            impl, bind = make_paged_attn_impl(
                block_tokens, backend, interpret, record,
                state_cols=1 if cfg.state_planes else 0)

        def fwd(p, inputs, cache, positions, logits_at,
                moe_stats=False, valid=None):
            return stage_forward(p, cfg, spec, inputs, cache, positions,
                                 attn_impl=impl, logits_at=logits_at,
                                 moe_stats=moe_stats, valid=valid)

        return fwd, bind, None
    require_one_kind(cfg, "tensor parallelism (--tp)")
    validate_tp(cfg, mesh)
    p_specs = _tp_param_specs(params_template, cfg)
    bound = {}

    def bind(tables, program: str):
        bound["tables"] = tables
        bound["program"] = program

    def fwd(p, inputs, cache, positions, logits_at,
            moe_stats=False, valid=None):
        program = bound["program"]

        def body(p_, i_, c_, po_, tab_, at_, valid_):
            impl, bind_local = make_paged_attn_impl(block_tokens, backend,
                                                    interpret, record)
            bind_local(tab_, program)
            return stage_forward(p_, cfg, spec, i_, c_, po_,
                                 tp_axis="tp", attn_impl=impl,
                                 logits_at=at_,
                                 moe_stats=moe_stats, valid=valid_)

        # ``None`` (``logits_at``, ``valid``) is an empty pytree: its
        # spec matches nothing
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, P(), _CACHE_SPEC, P(), P(), P(), P()),
            out_specs=(P(), _CACHE_SPEC) + ((P(),) if moe_stats else ()),
            check_vma=False)(p, inputs, cache, positions,
                             bound["tables"], logits_at, valid)

    return fwd, bind, tp_cache_sharding(mesh)


def make_tp_stage_fn(cfg: ModelConfig, spec: StageSpec, mesh: Mesh,
                     params_template: StageParams):
    """Jitted fn(params, inputs, cache, positions) -> (out, cache) with the
    stage's weights and KV cache sharded over ``tp`` (all-positions logits
    variant of :func:`make_tp_forward`).

    Requires ``cfg.num_kv_heads %% tp == 0`` (cache shards by kv head).
    Activations and logits come back replicated — the caller samples or
    forwards them without caring about the mesh.
    """
    fwd = make_tp_forward(cfg, spec, mesh, params_template)

    def fn(params, inputs, cache, positions):
        return fwd(params, inputs, cache, positions, None)

    return jax.jit(fn, donate_argnums=(2,))
