"""Kernels: the paged prefill calls' share of their roofline under a
summarised cache (``_paged_prefill_call_eva.<n>`` in the trace), as
``mla_prefill_kernel_roofline_pct`` reads the latent calls'.  Operations
and bytes come from the matched records' ``prefill_attended_rows`` (the
(query, row) pairs the slab's prompt tokens attend over: each its
window's earlier keys, itself and every closed window's summaries)
through the family's ``eva_prefill_kernel_ops`` /
``eva_prefill_kernel_bytes``: useful pairs only, where the kernel also
computes the masked ones of the pages on the causal edge and the rows of
a chunk that hold no token."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share
from layer_metrics.mla_prefill_kernel_roofline_pct import _chunk

KERNEL = "_paged_prefill_call_eva"


def read(ctx):
    chunk = _chunk(ctx)

    def bound(fam, mc, rec, peaks):
        pairs = rec["prefill_attended_rows"]
        return max(
            fam.eva_prefill_kernel_bytes(mc, pairs, chunk)
            / peaks["hbm_bytes_per_s"],
            fam.eva_prefill_kernel_ops(mc, pairs) / peaks["bf16_flops_per_s"])

    return bound_share(ctx, KERNEL, bound)
