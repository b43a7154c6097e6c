"""Kernels: the window kind's prefill calls' share of their roofline
(``_paged_prefill_call_window.<n>`` in the trace: the paged prefill
kernel over the pages a chunk and its window meet), as
``mla_prefill_kernel_roofline_pct`` reads the latent calls'.  Operations
and bytes come from the matched records' ``prefill_window_pairs`` (the
(query, key) pairs inside the window that the slab's prompt tokens attend
over) through the family's ``window_prefill_kernel_ops`` /
``window_prefill_kernel_bytes``: useful pairs only, where the kernel also
computes the masked ones of the tiles on the two edges of the window and
the rows of a chunk that hold no token."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share
from layer_metrics.mla_prefill_kernel_roofline_pct import _chunk

KERNEL = "_paged_prefill_call_window"


def read(ctx):
    chunk = _chunk(ctx)

    def bound(fam, mc, rec, peaks):
        pairs = rec["prefill_window_pairs"]
        return max(
            fam.window_prefill_kernel_bytes(mc, pairs, chunk)
            / peaks["hbm_bytes_per_s"],
            fam.window_prefill_kernel_ops(mc, pairs)
            / peaks["bf16_flops_per_s"])

    return bound_share(ctx, KERNEL, bound)
