"""Kernels: the latent prefill calls' share of their roofline
(``_paged_prefill_call_latent.<n>`` in the trace), as
``mla_decode_kernel_roofline_pct`` reads the decode calls'.  Operations
and bytes come from the matched records' ``prefill_kv_tokens`` (the
(query, cached token) pairs the slab's prompt tokens attend over) through
the family's ``mla_prefill_kernel_ops`` / ``mla_prefill_kernel_bytes``:
useful pairs only, where the kernel also computes the masked ones of the
tiles on the causal edge and the rows of a chunk that hold no token."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share

KERNEL = "_paged_prefill_call_latent"


def _chunk(ctx) -> int:
    flags = ctx["config"]["serve_flags"]
    return int(flags[flags.index("--prefill-chunk") + 1])


def read(ctx):
    chunk = _chunk(ctx)

    def bound(fam, mc, rec, peaks):
        pairs = rec["prefill_kv_tokens"]
        return max(
            fam.mla_prefill_kernel_bytes(mc, pairs, chunk)
            / peaks["hbm_bytes_per_s"],
            fam.mla_prefill_kernel_ops(mc, pairs) / peaks["bf16_flops_per_s"])

    return bound_share(ctx, KERNEL, bound)
