"""Core model abstractions: configs, KV cache, pipeline-stage parameter slices.

Design notes (TPU-first, not a port):

The reference splits an HF torch model into ``split_size`` sequential ONNX
"modules", one per device (reference ``server.py:831-832,893-905``).  Here a
model is a pure function over a parameter pytree whose per-layer weights are
*stacked* along a leading ``layer`` axis.  A pipeline stage ("module") is then
just ``jax.tree.map(lambda x: x[lo:hi], params.layers)`` — a zero-copy array
slice — and the per-stage forward is a single ``lax.scan`` over the stacked
layers, which XLA compiles into one fused loop that keeps the MXU busy.

The KV cache is first-class (the reference has none — SURVEY.md §2.7): a
preallocated head-major ``[layers, batch, kv_heads, max_seq, head_dim]`` pair
(see ``KVCache`` for why head-major) updated in place via
``lax.dynamic_update_slice`` with donated buffers, so decode steps are O(1)
in allocation and fully jit-compatible (static shapes).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def _yarn_tuple(yarn) -> tuple:
    """``(factor, original positions, beta_fast, beta_slow,
    attention_factor)`` as floats, from that tuple or from the JSON object
    a configuration file holds (HF's ``rope_scaling`` names); empty stays
    empty."""
    if isinstance(yarn, dict):
        yarn = (yarn["factor"], yarn["original_max_position_embeddings"],
                yarn["beta_fast"], yarn["beta_slow"],
                yarn["attention_factor"])
    return tuple(float(v) for v in yarn)


@dataclass(frozen=True)
class BlockKind:
    """What one KIND of block of a period has of its own: its attention
    (``attn`` "full", or "window" over the last ``window`` tokens: query
    ``i`` sees key ``j`` iff ``0 <= i - j < window``, or a STATE kind, no
    keys and no pages but a recurrent state a request behind a depthwise
    causal convolution of ``conv`` taps: "kda", the gated delta rule over
    ``[hd, hd]`` a head, ``ops.kda``; "ssd", Mamba-2's scalar decay a head
    over ``state_heads`` states of ``[state_head_dim, state_size]``, B and
    C shared by the heads of each of ``groups`` groups, the scan in chunks
    of ``chunk`` tokens, ``ops.ssd``), its query heads, its rope (``rope_theta``; ``rotary_share`` of a head's
    channels turn, the first ones, rotate-half within them, 0 = no rope;
    ``yarn`` = ``(factor, original positions, beta_fast, beta_slow,
    attention_factor)`` or empty: ``ops.rope.yarn_frequencies``) and its
    output gate ("none"; "per-head": each head's output times the sigmoid
    of a linear map of the block's normed input, one scalar a head, before
    ``wo``; "elementwise": the same with one scalar a CHANNEL of every
    head).  And which SUBLAYERS it has (docs/DESIGN.md section 31): a
    token mixer unless ``attn`` is "none", and the model's MLP (dense or
    experts) iff ``mlp``.  A block of both is ``x + mixer(norm x)`` then
    ``x + mlp(norm x)``; a block of ONE is one norm, one sublayer, one add
    (nemotron_h: a published layer is a Mamba-2 mixer, an attention or the
    experts).  A block without a mixer holds no cache at all: no plane of
    a page pool, none of the state pool.  Built from a JSON object
    (``ModelConfig.period``); hashable."""

    attn: str = "full"
    window: int = 0
    num_heads: int = 0
    rope_theta: float = 10000.0
    rotary_share: float = 1.0
    yarn: tuple = ()
    gate: str = "none"
    conv: int = 0
    state_heads: int = 0
    state_head_dim: int = 0
    state_size: int = 0
    groups: int = 1
    chunk: int = 0
    mlp: bool = True
    # RMSNorm on q and k a HEAD (weight ``[head_dim]``), before any rope
    qk_norm: bool = False
    # a "sparse" kind (docs/DESIGN.md section 32; ``ops.sparse_attention``):
    # keys pooled over ``sparse_kernel`` tokens every ``sparse_stride``
    # score blocks of ``sparse_block`` tokens, a query past
    # ``sparse_dense_len`` keeps the first ``sparse_init`` blocks, the
    # blocks of its last ``sparse_local`` tokens and the ``sparse_topk``
    # best of the others
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_block: int = 0
    sparse_topk: int = 0
    sparse_init: int = 0
    sparse_local: int = 0
    sparse_dense_len: int = 0

    @property
    def is_state(self) -> bool:
        """Its cache is a recurrent state a request, not rows of pages."""
        return self.attn in ("kda", "ssd", "lightning")

    @property
    def has_pages(self) -> bool:
        """Its cache is rows of a page pool (full keys and values)."""
        return self.attn in ("full", "window", "sparse")

    @property
    def sparse_sizes(self) -> tuple:
        """``(kernel, stride, block, topk, init, local, dense_len)``."""
        return (self.sparse_kernel, self.sparse_stride, self.sparse_block,
                self.sparse_topk, self.sparse_init, self.sparse_local,
                self.sparse_dense_len)

    @property
    def name(self) -> str:
        """What its parameter stacks and paths are named by: its ``attn``,
        "mlp" for a block that has no mixer."""
        return "mlp" if self.attn == "none" else self.attn

    def __post_init__(self):
        if self.attn not in ("full", "window", "kda", "ssd", "none",
                             "sparse", "lightning"):
            raise ValueError(f"a block kind's attn is 'full', 'window', "
                             f"'kda', 'ssd', 'sparse', 'lightning' or "
                             f"'none', got {self.attn!r}")
        if self.attn == "none" and not self.mlp:
            raise ValueError("a block kind has a token mixer (attn), an "
                             "MLP (mlp) or both: this one has neither "
                             "sublayer")
        if (self.attn == "window") != (self.window > 0):
            raise ValueError("a window kind states its window, a full "
                             "kind none")
        if (self.attn in ("kda", "ssd")) != (self.conv > 1):
            raise ValueError("a state kind behind a convolution (kda, ssd) "
                             "states its taps (conv >= 2), another kind "
                             "none")
        if self.attn == "sparse":
            kn, st, bl, topk, init, local, dense = self.sparse_sizes
            if not (st > 0 and kn >= st and kn % st == 0 and bl % st == 0
                    and topk > 0 and init >= 0 and local % bl == 0
                    and dense >= 0):
                raise ValueError(
                    "a sparse kind states sparse_kernel (a multiple of "
                    "sparse_stride), sparse_block (a multiple of the "
                    "stride), sparse_topk, sparse_init, sparse_local (whole "
                    f"blocks) and sparse_dense_len; got {self.sparse_sizes}")
        elif any(self.sparse_sizes):
            raise ValueError("only a sparse kind states sparse_* sizes")
        sizes = (self.state_heads, self.state_head_dim, self.state_size,
                 self.chunk)
        if not (all(v > 0 for v in sizes) if self.attn == "ssd"
                else not any(sizes)):
            raise ValueError("an ssd kind states state_heads, "
                             "state_head_dim, state_size and chunk, "
                             "another kind none of them")
        if self.attn == "ssd" and self.state_heads % self.groups:
            raise ValueError("an ssd kind's groups divide its state_heads")
        if self.gate not in ("none", "per-head", "elementwise"):
            raise ValueError(f"unknown gate {self.gate!r}")
        object.__setattr__(self, "yarn", _yarn_tuple(self.yarn))
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        object.__setattr__(self, "rotary_share", float(self.rotary_share))

    @staticmethod
    def of(spec) -> "BlockKind":
        return spec if isinstance(spec, BlockKind) else BlockKind(**spec)


@partial(jax.tree_util.register_dataclass,
         data_fields=[],
         meta_fields=["family", "vocab_size", "hidden_size", "num_layers",
                      "num_heads", "num_kv_heads", "intermediate_size",
                      "max_seq_len", "rope_theta", "norm_eps", "dtype_name",
                      "tie_embeddings", "use_alibi", "use_rope",
                      "attn_layernorm", "attn_qkv_bias", "num_experts",
                      "experts_per_token", "moe_capacity_factor",
                      "quantization", "head_dim_override", "embed_scale",
                      "mlp_act", "qk_norm", "norm_topk_prob", "ut_steps",
                      "sandwich_norm", "kv_lora_rank", "qk_nope_head_dim",
                      "qk_rope_head_dim", "v_head_dim", "lead_dense_layers",
                      "lead_intermediate_size", "num_shared_experts",
                      "router_scoring", "router_bias",
                      "routed_scaling_factor", "period", "lead_kind",
                      "experts_held", "norm_unit_offset", "fp32_residual",
                      "fp32_logits", "eva_window", "eva_chunk",
                      "num_pred_heads", "hc_streams", "hc_sinkhorn_iters",
                      "hc_eps", "hc_res_clamp", "q_lora_rank", "yarn",
                      "attn_scale", "embedding_multiplier",
                      "residual_multiplier", "logits_scaling"])
@dataclass(frozen=True)
class ModelConfig:
    """Static, hashable architecture description shared by all model families.

    ``family`` selects the block flavor ("llama", "bloom", "mixtral", ...).
    The feature flags (rope/alibi/gated-mlp) let one decoder implementation
    cover the whole catalog the reference supports (bloom560m..7b1,
    reference ``data/Data.kt:19-33``) plus the BASELINE.json targets
    (TinyLlama, Llama-3-8B, Mixtral-8x7B).
    """

    family: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 2048
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    intermediate_size: int = 5632
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype_name: str = "bfloat16"
    tie_embeddings: bool = False
    # bloom-style ALiBi positional bias vs llama-style RoPE
    use_alibi: bool = False
    use_rope: bool = True
    # bloom uses LayerNorm (with bias); llama uses RMSNorm
    attn_layernorm: bool = False
    # qwen2-style: q/k/v projections carry biases (RMSNorm model, so
    # independent of attn_layernorm, which implies ALL attention biases)
    attn_qkv_bias: bool = False
    # gemma: head_dim decoupled from hidden/heads (0 = derive), embedding
    # scaled by sqrt(hidden), and a non-silu gated-MLP activation
    head_dim_override: int = 0
    embed_scale: bool = False
    mlp_act: str = "silu"      # "silu" | "gelu_tanh" (gemma)
    # MoE (mixtral): 0 experts means dense MLP
    num_experts: int = 0
    experts_per_token: int = 2
    # the router's order: softmax over ALL experts, the k largest, then
    # renormalise the k to sum to 1 iff ``norm_topk_prob`` (mixtral;
    # the same arithmetic as its "top-k then softmax").  olmoe keeps the
    # probabilities as they are: a token's k weights sum to less than 1
    norm_topk_prob: bool = True
    # olmoe: RMSNorm over the WHOLE q and k projections (all heads'
    # channels in one mean square), before the head split and rope
    qk_norm: bool = False
    # expert-parallel dispatch capacity: slots per expert =
    # ceil(tokens * k / num_experts * factor); over-capacity tokens drop
    moe_capacity_factor: float = 2.0
    # weight-only quantization: "none" | "int8" | "int4" (ops/quant.py)
    quantization: str = "none"
    # ouro (looped decoder): the whole layer stack runs ``ut_steps`` times
    # a token with the same weights, the final norm closing every pass;
    # a pass attends to its OWN keys and values, so the cache holds
    # ``kv_planes`` planes, pass ``t``'s layer ``l`` at ``t * L + l``
    ut_steps: int = 1
    # ouro: RMSNorm on each sublayer's OUTPUT too, before the residual
    # add (four norms a block)
    sandwich_norm: bool = False
    # deepseek_v3 (multi-head latent attention): a token's cache is the
    # normed latent ``c`` (``kv_lora_rank``) and one roped key part
    # ``k_pe`` (``qk_rope_head_dim``) that every head shares; a head's
    # query is [no-rope ``qk_nope_head_dim`` | rope], its value
    # ``v_head_dim`` wide, rope pairs interleaved (2i, 2i+1), softmax
    # scale (nope + rope) ** -0.5.  0 = full keys and values
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # deepseek_v3: ``lead_dense_layers`` blocks with a dense SwiGLU of
    # width ``lead_intermediate_size`` run once BEFORE the repeated
    # stack; ``num_layers`` counts the repeated stack alone
    lead_dense_layers: int = 0
    lead_intermediate_size: int = 0
    # shared experts: one dense SwiGLU of width ``num_shared_experts x
    # intermediate_size`` on every row, added to the routed sum
    num_shared_experts: int = 0
    # the router's scores: "softmax" over all experts, or "sigmoid" of
    # each logit (deepseek_v3 ``noaux_tc``: with ``router_bias`` the k
    # experts are CHOSEN by score + a stored per-expert bias and WEIGHED
    # by the score alone); the k weights, renormalised iff
    # ``norm_topk_prob``, times ``routed_scaling_factor``
    router_scoring: str = "softmax"
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    # a PERIOD of unlike blocks (docs/DESIGN.md section 25): the repeated
    # stack is ``num_layers`` repeats of these kinds in order, so
    # ``num_layers`` counts repeats and a model of one kind of block (the
    # empty period: its attention is the flat fields above) counts what it
    # always did.  Each entry a :class:`BlockKind` or the JSON object of
    # one; ``lead_kind`` is the kind of the leading dense blocks.
    # ``StageParams.layers`` then holds one stack a kind, ``<leaf>.<kind
    # name>`` of shape ``[repeats, blocks of that kind in a period, ...]``
    period: tuple = ()
    lead_kind: Optional[BlockKind] = None
    # this chip's share of the routed experts, ``(held, first)``: the
    # router scores all ``num_experts``, the expert stacks hold experts
    # ``[first, first + held)`` and a row routed elsewhere enters no
    # group; what the absent experts would add is left out (no psum, no
    # stand-in).  Empty: every expert is here
    experts_held: tuple = ()
    # evabyte: RMSNorm's gain is ``1 + w`` (``norm_add_unit_offset``), the
    # residual stream rides the layers in float32 (``fp32_skip_add``: the
    # norms' outputs and every matmul keep the model's dtype) and the head
    # runs in float32 on the float32 normed row (``fp32_logits``)
    norm_unit_offset: bool = False
    fp32_residual: bool = False
    fp32_logits: bool = False
    # evabyte (EVA attention, docs/DESIGN.md section 26): a query at
    # position ``i`` sees the exact keys of its own window (``j <= i``,
    # ``j // eva_window == i // eva_window``) and every EARLIER window as
    # ``eva_window / eva_chunk`` summaries, one learned-pooled key and
    # value a chunk of ``eva_chunk`` tokens, in one softmax.  0 = every
    # key exact
    eva_window: int = 0
    eva_chunk: int = 0
    # evabyte: the head holds ``num_pred_heads x vocab_size`` rows (head
    # ``n`` predicts byte ``t + n``); the served path reads the first
    # ``vocab_size``, the next-byte head
    num_pred_heads: int = 1
    # xing4_0 (manifold-constrained hyper-connections, docs/DESIGN.md
    # section 28): a token rides the blocks as ``hc_streams`` residual
    # streams of ``hidden_size`` side by side.  Each sublayer reads them
    # through a sigmoid map ``[n]``, writes back through ``2 x`` a sigmoid
    # map ``[n]`` and mixes them by an ``[n, n]`` map made doubly
    # stochastic by ``hc_sinkhorn_iters`` column-then-row normalisations
    # of ``exp(clip(. , -+hc_res_clamp))`` with ``hc_eps`` in both
    # denominators, all three computed from the token's own streams
    # (``ops.hyper_connection``).  0 = one stream, the path every other
    # model takes, untouched
    hc_streams: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # deepseek_v3's low-rank query: ``q = rmsnorm(h W_qa, g_q) W_qb`` with
    # ``W_qa`` ``[H, q_lora_rank]``.  0 = q in one matrix
    q_lora_rank: int = 0
    # the latent kind's rope: ``yarn`` = ``(factor, original positions,
    # beta_fast, beta_slow, attention_factor)`` as ``BlockKind.yarn`` (on
    # the ``qk_rope_head_dim`` lanes, interleaved pairs), or empty; and
    # what multiplies its softmax scale ``(nope + rope) ** -0.5``
    # (deepseek's ``mscale ** 2`` under YaRN)
    yarn: tuple = ()
    attn_scale: float = 1.0
    # granite's multipliers, 1.0 for every other family (a static branch:
    # their programs hold no multiply): the embedded rows times
    # ``embedding_multiplier``, each sublayer's output times
    # ``residual_multiplier`` before the residual add, the head's logits
    # divided by ``logits_scaling``; its fourth, the softmax scale, is
    # ``attn_scale`` above (times ``head_dim ** -0.5``), which a block of
    # full keys and values reads too
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    def __post_init__(self):
        object.__setattr__(self, "yarn", _yarn_tuple(self.yarn))
        object.__setattr__(self, "period",
                           tuple(BlockKind.of(k) for k in self.period))
        if self.lead_kind is not None:
            object.__setattr__(self, "lead_kind",
                               BlockKind.of(self.lead_kind))
        object.__setattr__(self, "experts_held",
                           tuple(int(v) for v in self.experts_held))

    @property
    def dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype_name)

    @property
    def total_layers(self) -> int:
        """Every block a token passes: the leading dense ones and the
        repeated stack (``num_layers`` repeats of the period)."""
        return (self.lead_dense_layers
                + self.num_layers * max(1, len(self.period)))

    @property
    def experts_here(self) -> int:
        """Routed experts whose matrices this chip holds."""
        return self.experts_held[0] if self.experts_held else self.num_experts

    @property
    def mlp_blocks(self) -> int:
        """Blocks of the repeated stack that have the model's MLP (its
        experts, where it has any): every block, but for a period of
        blocks of one sublayer (``BlockKind.mlp``)."""
        if not self.period:
            return self.num_layers
        return self.num_layers * sum(k.mlp for k in self.period)

    @property
    def kinds(self) -> tuple:
        """The period's distinct kinds as ``(name, kind, positions)``, in
        order of first appearance: ``positions`` the kind's places in the
        period, ``name`` its ``BlockKind.name`` (with the first place
        appended where two kinds share one).  The parameter stacks are
        named by it."""
        seen = []
        for p, k in enumerate(self.period):
            for entry in seen:
                if entry[1] == k:
                    entry[2].append(p)
                    break
            else:
                seen.append([k.name, k, [p]])
        names = [e[0] for e in seen]
        return tuple((n if names.count(n) == 1 else f"{n}{pos[0]}", k,
                      tuple(pos)) for n, k, pos in seen)

    @property
    def cache_kinds(self) -> tuple:
        """The cache spec by block kind, ``(window, planes)`` a POOL: how
        many tokens back a block of the pool reads (0 = all) and the
        planes a token holds there.  Blocks that read alike share a pool,
        the full kind's (the one that fills) first; a model of one kind of
        block has one entry, ``(0, kv_planes)``."""
        if not self.period:
            return ((0, self.kv_planes),)
        count = {}
        if self.lead_kind is not None and self.lead_dense_layers:
            count[self.lead_kind.window] = self.lead_dense_layers
        for k in self.period:
            if k.has_pages:     # not a state (below), not a block of none
                count[k.window] = count.get(k.window, 0) + self.num_layers
        return tuple(sorted(count.items()))

    @property
    def state_kind(self) -> Optional[BlockKind]:
        """The period's state kind (kda or ssd; ``BlockKind.is_state``), or
        None.  One state pool holds every such block's plane, so a period
        has one state kind: planes of two shapes are refused here."""
        kinds = {k for k in self.period if k.is_state}
        if len(kinds) > 1:
            raise ValueError(
                f"a period holds one state kind, its blocks' planes share "
                f"a pool; got {sorted(k.attn for k in kinds)}")
        return next(iter(kinds), None)

    @property
    def sparse_kind(self) -> Optional[BlockKind]:
        """The period's block-sparse kind (``attn == "sparse"``), or None.
        Its blocks' pages lie in the full kind's pool, and beside that pool
        rides ONE index plane array (:meth:`index_shape`), so a period has
        one sparse kind."""
        kinds = {k for k in self.period if k.attn == "sparse"}
        if len(kinds) > 1:
            raise ValueError("a period holds one sparse kind: its blocks "
                             "share one index plane's shape")
        if kinds and self.num_experts > 0:
            raise ValueError(
                "a model with a sparse kind has no experts: a block's row "
                "of counters in the serving programs holds what its "
                "selections kept where a block with experts counts the "
                "rows routed to each")
        return next(iter(kinds), None)

    def index_shape(self, num_pages: int, block_tokens: int) -> tuple:
        """The index plane beside the full kind's page pool, ``[planes,
        pages x pooled keys a page, kv heads x head_dim]`` (rows of whole
        lanes along a leading axis: a gather or a scatter of rows moves
        them as they lie): page ``p``'s row ``i`` (row ``p x bt / stride +
        i``) is the mean, a kv head, of the ``sparse_kernel`` keys from
        token ``i x sparse_stride`` of the page on (they may run into the
        next page of the request), written when the last of them is.  A plane a plane
        of the pool, addressed by the same table: a page's index rows are
        leased and freed with it.  It rides ``keys`` after the pools of
        pages (and before a state pool), a placeholder of one element in
        its place among ``values``."""
        kind = self.sparse_kind
        if block_tokens % kind.sparse_block:
            raise ValueError(
                f"a page holds whole blocks of the sparse kind: "
                f"--kv-block-tokens {block_tokens} is no multiple of "
                f"{kind.sparse_block}")
        return (self.cache_kinds[0][1],
                num_pages * (block_tokens // kind.sparse_stride),
                self.num_kv_heads * self.head_dim)

    @property
    def sparse_blocks(self) -> int:
        """Blocks of the sparse kind (each a plane of the index plane)."""
        return self.num_layers * sum(k.attn == "sparse" for k in self.period)

    @property
    def cache_arrays(self) -> int:
        """Arrays of ``keys`` (and of ``values``) before a state pool: one
        a pool of pages, and a sparse kind's index plane."""
        return len(self.cache_kinds) + (self.sparse_kind is not None)

    @property
    def state_planes(self) -> int:
        """Blocks whose cache is a recurrent STATE a request (a state
        kind, docs/DESIGN.md sections 27 and 29), not rows of a page pool:
        repeat ``r``'s ``j``-th such place holds plane ``r x (places a
        period) + j``."""
        return self.num_layers * sum(k.is_state for k in self.period)

    @property
    def state_shapes(self) -> tuple:
        """``(state, tail)`` of one request's entry in one state plane, by
        the state KIND: the float32 state a head and the convolution's
        tail, the last ``taps - 1`` inputs of the convolved channels (the
        model's dtype).  kda: ``(heads, hd, hd)`` ``[key, value]`` and the
        q, k and v channels side by side, ``3 x heads x hd``; ssd:
        ``(state_heads, state_head_dim, state_size)`` and the ``taps - 1``
        inputs of the ``x | B | C`` channels (``state_heads x
        state_head_dim + 2 x groups x state_size``) END TO END in one row,
        ``((taps - 1) x channels,)``: three rows of channels are a padded
        tile on the chip (16 sublanes for 3) that every gather and scatter
        of a row re-laid, 46 ms a dispatch of copies of the whole pool (my
        chip run, PR 62); a request's row of lanes is not."""
        kind = self.state_kind
        if kind.attn == "lightning":
            # ``[value, key]`` a head (``ops.ssd``'s ``[P, N]``), and no
            # convolution: one element stands where a tail would
            return ((kind.num_heads, self.head_dim, self.head_dim), (1,))
        if kind.attn == "ssd":
            return ((kind.state_heads, kind.state_head_dim,
                     kind.state_size),
                    ((kind.conv - 1) * (
                        kind.state_heads * kind.state_head_dim
                        + 2 * kind.groups * kind.state_size),))
        hd = self.head_dim
        return ((kind.num_heads, hd, hd),
                (kind.conv - 1, 3 * kind.num_heads * hd))

    @property
    def state_bytes_per_slot(self) -> int:
        """What one request holds in the state pool, whatever its length."""
        if not self.state_planes:
            return 0
        s_shape, c_shape = self.state_shapes
        return self.state_planes * (math.prod(s_shape) * 4 + math.prod(
            c_shape) * self.dtype.itemsize)

    def state_arrays(self, keys, values) -> tuple:
        """``(state pool, convolution tails)`` out of a cache's ``keys`` and
        ``values``: THE one place that says where a recurrent state rides,
        and checks it.  The state is not a field of its own (docs/DESIGN.md
        section 27 says why): the pool ``[state_planes, rows,
        *state_shapes[0]]`` float32 is the LAST entry of ``keys`` after
        one pool of pages a cache kind, the tails ``[state_planes, rows,
        *state_shapes[1]]`` the last of ``values``; a row's row of the pool is its
        table's last column, after one table a kind side by side
        (``ops.paged_attention``'s ``impl.for_state``)."""
        kinds = self.cache_arrays
        s_shape, c_shape = self.state_shapes
        state, tails = keys[-1], values[-1]
        if (len(keys) != kinds + 1 or len(values) != kinds + 1
                or state.dtype != jnp.float32
                or state.shape[:1] + state.shape[2:]
                != (self.state_planes,) + s_shape
                or tails.shape[:1] + tails.shape[2:]
                != (self.state_planes,) + c_shape
                or state.shape[1] != tails.shape[1]):
            raise ValueError(
                f"a cache of {kinds} array(s) of pages and, last, a float32 "
                f"state pool [{self.state_planes}, rows, *{s_shape}] with "
                f"its tails [{self.state_planes}, rows, *{c_shape}] was "
                f"expected; got keys "
                f"{[(tuple(k.shape), str(k.dtype)) for k in keys]}, values "
                f"{[tuple(v.shape) for v in values]}")
        return state, tails

    def plane_of(self, block: int) -> tuple:
        """``(pool, plane)`` of block ``block`` (the leading blocks first,
        then the repeats of the period in order): the index of its pool in
        ``cache_kinds`` and its plane there.  Within a pool the leading
        blocks' planes come first, then repeat by repeat.  A block of a
        state kind holds no pages: ``(-1, its plane of the state pool)``;
        a block without a mixer holds nothing: ``(None, None)``."""
        windows = [w for w, _ in self.cache_kinds]
        lead = self.lead_dense_layers
        if block < lead:
            return windows.index(self.lead_kind.window), block
        r, p = divmod(block - lead, len(self.period))
        if self.period[p].attn == "none":
            return None, None
        if self.period[p].is_state:         # a plane of the state pool
            mine = [q for q, k in enumerate(self.period) if k.is_state]
            return -1, r * len(mine) + mine.index(p)
        w = self.period[p].window
        mine = [q for q, k in enumerate(self.period)
                if k.window == w and k.has_pages]
        base = lead if (self.lead_kind is not None
                        and self.lead_kind.window == w) else 0
        return windows.index(w), base + r * len(mine) + mine.index(p)

    def of_kind(self, kind: BlockKind) -> "ModelConfig":
        """The configuration of ONE block of ``kind``: its heads and rope
        in the flat fields, the kind itself as a period of one."""
        return self.replace(num_heads=kind.num_heads or self.num_heads,
                            rope_theta=kind.rope_theta, period=(kind,),
                            lead_kind=None, lead_dense_layers=0,
                            num_layers=1)

    @property
    def block_kind(self) -> Optional[BlockKind]:
        """The one kind of a single-kind configuration (``of_kind``)."""
        return self.period[0] if len(self.period) == 1 else None

    @property
    def kv_planes(self) -> int:
        """K/V planes a token holds: one a layer a pass (the leading
        dense blocks first); in a period model one a block that holds
        pages (a state kind's block and a block without a mixer hold
        none), over its pools.  The one source of every KV structure's
        plane count (dense cache, page pool, host tier, exported
        blocks)."""
        if self.period:
            return sum(planes for _, planes in self.cache_kinds)
        return self.total_layers * self.ut_steps

    @property
    def mixed_kinds(self) -> bool:
        """A period model: its blocks are of more than one kind (each with
        its own heads, rope, mask and cache)."""
        return bool(self.period)

    @property
    def latent_kv(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def summary_kv(self) -> bool:
        """A cache of two roles of row (``eva_window``): a window of
        exact keys and values, and a summary a chunk of every window that
        closed.  What a row attends is then a function of its position,
        not its token count (:func:`eva_rows`)."""
        return self.eva_window > 0

    @property
    def latent_scale(self) -> float:
        """The latent kind's softmax scale: ``(nope + rope) ** -0.5``
        times ``attn_scale``."""
        return ((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
                * self.attn_scale)

    @property
    def hc_maps(self) -> int:
        """Coefficients a sublayer's three maps hold: ``2 n + n ** 2``."""
        n = self.hc_streams
        return 2 * n + n * n

    @property
    def hc_args(self) -> dict:
        """What ``ops.hyper_connection.hc_pre`` is told of the maps."""
        return dict(n=self.hc_streams, iters=self.hc_sinkhorn_iters,
                    eps=self.hc_eps, clamp=self.hc_res_clamp,
                    norm_eps=self.norm_eps)

    @property
    def kv_streams(self) -> int:
        """Tensors a token holds in each plane: keys and values, or the
        one latent row."""
        return 1 if self.latent_kv else 2

    @property
    def kv_page_shape(self) -> tuple:
        """``(heads, width)`` of one token's entry in a plane of the page
        pool: every kv head's ``head_dim``, or the one latent row
        ``[c | k_pe]`` padded to whole lanes
        (``ops.latent_attention.latent_page_width``)."""
        if self.latent_kv:
            from ..ops.latent_attention import latent_page_width
            return 1, latent_page_width(self.kv_lora_rank,
                                        self.qk_rope_head_dim)
        return self.num_kv_heads, self.head_dim

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.hidden_size // self.num_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class StageSpec:
    """A contiguous layer range assigned to one pipeline stage/worker.

    Mirrors the role of a reference "module" (``server.py:893-905``): the
    first stage owns the embedding, the last owns the final norm + LM head.
    """

    stage_id: int
    num_stages: int
    layer_start: int
    layer_end: int  # exclusive

    @property
    def is_first(self) -> bool:
        return self.stage_id == 0

    @property
    def is_last(self) -> bool:
        return self.stage_id == self.num_stages - 1

    @property
    def num_layers(self) -> int:
        return self.layer_end - self.layer_start


@partial(jax.tree_util.register_dataclass,
         data_fields=["keys", "values", "length"], meta_fields=[])
@dataclass
class KVCache:
    """Per-stage KV cache: stacked over the stage's planes (its layers,
    once for each pass of a looped model: ``ModelConfig.kv_planes``).

    keys/values: ``[planes, batch, num_kv_heads, max_seq, head_dim]``
    — **head-major**, so each kv head's cache is a contiguous ``[seq, hd]``
    plane: the layout the Pallas flash kernel streams HBM→VMEM per head,
    and the one XLA tiles best (the trailing ``[seq, hd]`` dims map onto
    (sublane, lane) without a relayout).
    ``length`` is a scalar int32 tracking how many positions are filled.

    Capacity is NOT checked inside traced code (``dynamic_update_slice``
    clamps silently) — the engine layer enforces
    ``prompt_len + new_tokens <= max_seq`` host-side, where both are static.
    """

    keys: jax.Array
    values: jax.Array
    length: jax.Array

    @staticmethod
    def create(cfg: ModelConfig, num_layers: int, batch: int,
               max_seq: Optional[int] = None, dtype=None) -> "KVCache":
        # requested capacity is a lower bound: the buffer is padded to the
        # sublane granule HERE, at the one choke point, so no engine can
        # reintroduce the flash kernel's divisible-by-8 crash by forgetting
        # to pad (see pad_cache_capacity below)
        max_seq = pad_cache_capacity(max_seq or cfg.max_seq_len)
        dtype = dtype or cfg.dtype
        # ``num_layers`` is the stage's layer count; a looped model
        # (one stage only) holds each of them ``ut_steps`` times:
        # cfg.kv_planes for the whole model (a model with leading dense
        # blocks runs on one stage too, and they hold the first planes).
        # A latent-attention model's cache is ``keys`` alone, one row a
        # token; ``values`` holds no element
        heads, width = cfg.kv_page_shape
        if cfg.period:
            # one stack a pool (``cfg.cache_kinds``); dense, so a window
            # kind keeps every token and its mask bounds the view
            zeros = lambda: tuple(
                jnp.zeros((planes, batch, heads, max_seq, width), dtype)
                for _, planes in cfg.cache_kinds)
            keys, values = zeros(), zeros()
            if cfg.state_planes:
                # the recurrent state rides last in ``keys`` and its
                # convolution tail last in ``values``, a row a sequence
                s_shape, c_shape = cfg.state_shapes
                keys += (jnp.zeros((cfg.state_planes, batch) + s_shape,
                                   jnp.float32),)
                values += (jnp.zeros((cfg.state_planes, batch) + c_shape,
                                     cfg.dtype),)
            return KVCache(keys=keys, values=values,
                           length=jnp.zeros((), jnp.int32))
        shape = ((num_layers + cfg.lead_dense_layers) * cfg.ut_steps,
                 batch, heads, max_seq)
        return KVCache(
            keys=jnp.zeros(shape + (width,), dtype),
            values=jnp.zeros(
                shape + (0 if cfg.latent_kv else width,), dtype),
            length=jnp.zeros((), jnp.int32),
        )

    @property
    def max_seq(self) -> int:
        return jax.tree.leaves(self.keys)[0].shape[3]


def pad_cache_capacity(n: int) -> int:
    """KV buffer capacity rounded up to the TPU sublane granule (8).

    The flash kernel streams [block_k, head_dim] K/V tiles whose sublane
    dimension must divide into the cache's sequence axis in multiples of 8
    (``ops/flash_attention.py:_pick_block``), so every engine allocates its
    cache a few slots larger than the user-facing ``max_seq`` bound when
    that bound isn't already aligned.  Purely a buffer-shape concern: the
    extra slots sit beyond every valid length and stay masked (the same
    stale-slot invariant that covers speculative rollback and batching
    admission), and the capacity CHECK (``check_capacity``) still enforces
    the caller's ``max_seq``."""
    return -(-n // 8) * 8


@partial(jax.tree_util.register_dataclass,
         data_fields=["layers", "embed", "final_norm", "lm_head", "lead"],
         meta_fields=[])
@dataclass
class StageParams:
    """Parameters owned by one pipeline stage.

    ``layers`` is a dict of stacked arrays with leading dim = stage layer
    count.  ``embed`` / ``final_norm`` / ``lm_head`` are present only on the
    stages that own them (first / last), else None.
    """

    layers: dict
    embed: Optional[dict] = None
    final_norm: Optional[dict] = None
    lm_head: Optional[dict] = None
    # the leading dense blocks' leaves (``ModelConfig.lead_dense_layers``),
    # stacked like ``layers``; an empty tree for every other model
    lead: Optional[dict] = None

    def nbytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(
            (self.layers, self.embed, self.final_norm, self.lm_head,
             self.lead)))


def require_kv_pair(cfg: ModelConfig, what: str) -> None:
    """Refuse a latent-attention model (``kv_lora_rank > 0``) where
    ``what`` is built for a pair of key and value tensors of one head
    size: called where such a thing is built, so the model is refused in
    a sentence and never run wrongly."""
    if cfg.latent_kv:
        raise ValueError(
            f"{what} does not support a latent-attention model (family "
            f"{cfg.family!r}, kv_lora_rank={cfg.kv_lora_rank}): a token's "
            f"cache is one latent row a layer, and it is built for keys "
            f"and values of one head size. Serve it on one chip with bf16 "
            f"pages (serve --batch-slots)")


def require_one_kind(cfg: ModelConfig, what: str) -> None:
    """Refuse a model of more than one kind of block (``period``) where
    ``what`` is built for one page table, one pool and one head count for
    the whole stack: called where such a thing is built, so the model is
    refused in a sentence and never run wrongly."""
    if cfg.mixed_kinds:
        raise ValueError(
            f"{what} does not support a model of more than one kind of "
            f"block (family {cfg.family!r}, a period of "
            f"{len(cfg.period)}): its blocks differ in heads, rope, mask "
            f"and cache, and it is built for one of each. Serve it on one "
            f"chip with bf16 pages through the mixed dispatch (serve "
            f"--batch-slots --prefill-chunk --mixed-token-budget)")


def require_no_state(cfg: ModelConfig, what: str) -> None:
    """Refuse a model with a recurrent state a request (a state kind of
    block, kda or ssd: ``state_planes > 0``) where ``what`` is built for a cache that
    is rows of tokens: a prefix's state is not a block of tokens that can
    be shared, exported or rolled back, and a rejected token has already
    moved it.  ``require_token_rows`` asks it first, so whatever refuses
    a summarised cache refuses a state; called by name only where a state
    alone is in the way (the engine's serialized interleave, speculation
    and ``--tp``)."""
    if cfg.state_planes:
        raise ValueError(
            f"{what} does not support a model with a recurrent state "
            f"(family {cfg.family!r}, {cfg.state_planes} "
            f"{cfg.state_kind.attn} blocks): a "
            f"request's state is {cfg.state_bytes_per_slot} bytes that "
            f"every token rewrites, not rows a token that stay where they "
            f"were written, and it is built for those. Serve it on one "
            f"chip with bf16 pages and a float32 state through the mixed "
            f"dispatch (serve --batch-slots --prefill-chunk "
            f"--mixed-token-budget)")


def eva_rows(window: int, chunk: int, n: int) -> tuple:
    """``(summary rows, exact rows)`` that hold ``n`` tokens under EVA
    attention (``ModelConfig.eva_window`` / ``eva_chunk``): the summaries
    of every closed window, ``window / chunk`` each, and the exact keys
    of the open one.  The query at position ``t`` attends ``eva_rows(...,
    t + 1)``: at a multiple of ``window`` its own key alone is exact.
    Host arithmetic (the scheduler's reservations and records), the same
    function of ``t`` the device builds its tables from."""
    if n <= 0:
        return 0, 0
    closed = (n - 1) // window
    return closed * (window // chunk), n - closed * window


def require_token_rows(cfg: ModelConfig, what: str) -> None:
    """Refuse a model whose cache is not a row a token that stays where it
    was written, where ``what`` is built for one: a recurrent state a
    request (``require_no_state``'s sentence), or a window of exact rows
    plus a summary a chunk (``eva_window > 0``).  Called where such a
    thing is built, so the model is refused in a sentence and never run
    wrongly."""
    require_no_state(cfg, what)
    if cfg.summary_kv:
        raise ValueError(
            f"{what} does not support a model with a summarised cache "
            f"(family {cfg.family!r}, eva_window={cfg.eva_window}, "
            f"eva_chunk={cfg.eva_chunk}): a window's pages are written "
            f"again by the next window and every closed window is "
            f"{cfg.eva_window // max(1, cfg.eva_chunk)} summary rows, and "
            f"it is built for a row a token. Serve it on one chip with "
            f"bf16 pages through the mixed dispatch (serve --batch-slots "
            f"--prefill-chunk --mixed-token-budget)")


def require_one_stream(cfg: ModelConfig, what: str) -> None:
    """Refuse a model with more than one residual stream (``hc_streams``)
    where ``what`` is built for one ``[b, s, H]`` row a token between
    blocks, or has never compiled the stream's two kernels: called where
    such a thing is built, so the model is refused in a sentence and
    never run wrongly."""
    if cfg.hc_streams:
        raise ValueError(
            f"{what} does not support a model with {cfg.hc_streams} "
            f"residual streams (family {cfg.family!r}, hc_streams="
            f"{cfg.hc_streams}): a token rides its blocks as "
            f"{cfg.hc_streams} x {cfg.hidden_size} values that every "
            f"sublayer mixes, and it is built for one row of "
            f"{cfg.hidden_size}. Serve it on one chip, on one stage, "
            f"through the mixed dispatch (serve --batch-slots "
            f"--prefill-chunk --mixed-token-budget)")


def require_single_pass(cfg: ModelConfig, what: str) -> None:
    """Refuse a looped model (``ut_steps > 1``) where ``what`` visits a
    layer once: a stage of a pipeline owns a layer range and would have
    to be visited once a pass, a draft or a sequence-parallel forward
    sizes its cache by layers.  Called where such a thing is built, so a
    looped model is refused in a sentence and never run wrongly."""
    if cfg.ut_steps > 1:
        raise ValueError(
            f"{what} does not support a looped model (family "
            f"{cfg.family!r}, ut_steps={cfg.ut_steps}): every pass would "
            f"have to visit it again, and it is built to be visited once. "
            f"Serve it on one stage (serve --batch-slots, with or without "
            f"--tp)")


def slice_stage(full: StageParams, cfg: ModelConfig, spec: StageSpec) -> StageParams:
    """Cut a full-model StageParams into the slice owned by ``spec``.

    This is the TPU-native equivalent of the reference's per-module ONNX
    export + zip + ship (``server.py:910-957``): shard manifests instead of
    ONNX zips, realized as array slices.
    """
    if spec.num_stages > 1:
        require_token_rows(cfg, "a pipeline of stages")
        require_one_kind(cfg, "a pipeline of stages")
        require_single_pass(cfg, "a pipeline of stages")
        require_kv_pair(cfg, "a pipeline of stages")
        require_one_stream(cfg, "a pipeline of stages")
    layers = jax.tree.map(lambda x: x[spec.layer_start:spec.layer_end], full.layers)
    # Tied embeddings: the last stage needs the token table for the LM head.
    needs_embed = spec.is_first or (spec.is_last and cfg.tie_embeddings)
    return StageParams(
        layers=layers,
        embed=full.embed if needs_embed else None,
        final_norm=full.final_norm if spec.is_last else None,
        lm_head=full.lm_head if spec.is_last else None,
        lead=full.lead if spec.is_first else None,
    )


def split_layer_ranges(num_layers: int, num_stages: int,
                       weights: Optional[list] = None) -> list:
    """Partition ``num_layers`` into ``num_stages`` contiguous ranges.

    With ``weights`` (per-layer cost, e.g. FLOPs from the cost model), uses a
    balanced greedy prefix split; otherwise an even split.  Returns a list of
    StageSpec.  Replaces the reference's round_robin_module_arrangement
    (``server.py:893-905``).
    """
    if num_stages > num_layers:
        raise ValueError(
            f"cannot split {num_layers} layers into {num_stages} stages")
    if weights is None:
        weights = [1.0] * num_layers
    if len(weights) != num_layers:
        raise ValueError("weights must have one entry per layer")

    # Dynamic programming over cut points minimizing the max stage cost
    # (the pipeline's throughput is set by its slowest stage).  O(S * L^2)
    # with L = model depth — trivial at planning time.
    prefix = [0.0]
    for w in weights:
        prefix.append(prefix[-1] + float(w))

    def cost(i, j):  # cost of layers [i, j)
        return prefix[j] - prefix[i]

    INF = float("inf")
    # best[s][j] = minimal max-stage-cost splitting layers [0, j) into s
    # stages of >= 1 layer each; cut[s][j] = the last cut position.
    best = [[INF] * (num_layers + 1) for _ in range(num_stages + 1)]
    cut = [[0] * (num_layers + 1) for _ in range(num_stages + 1)]
    best[0][0] = 0.0
    for s in range(1, num_stages + 1):
        for j in range(s, num_layers + 1):
            for i in range(s - 1, j):
                c = max(best[s - 1][i], cost(i, j))
                if c < best[s][j]:
                    best[s][j] = c
                    cut[s][j] = i
    bounds = [num_layers]
    j = num_layers
    for s in range(num_stages, 0, -1):
        j = cut[s][j]
        bounds.append(j)
    bounds.reverse()

    specs = []
    for s in range(num_stages):
        specs.append(StageSpec(stage_id=s, num_stages=num_stages,
                               layer_start=bounds[s], layer_end=bounds[s + 1]))
    assert all(sp.num_layers >= 1 for sp in specs)
    return specs
