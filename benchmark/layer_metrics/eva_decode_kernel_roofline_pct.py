"""Kernels: the paged decode calls' share of their roofline under a
summarised cache (``_paged_call_eva.<n>`` in the trace: the paged decode
kernel over the table of summary pages then window pages that the
program builds from a row's position), as
``mla_decode_kernel_roofline_pct`` reads the latent calls'.  Operations
and bytes come from the matched records' ``kv_attended_rows`` (the sum
over the rows that decoded of the rows of the pool their query attends: a
page of summaries a closed window and the open window's exact keys; NOT
the tokens they hold, which ``decode_kernel_hbm_pct`` multiplies and reads
several times over here) and ``steps`` through the family's
``eva_decode_kernel_ops`` / ``eva_decode_kernel_bytes``.  Bytes count rows
where the kernel reads whole pages, so the share reads under, never over.
``None`` without the kernel in the trace, the column in the records, or
the family's functions."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share

KERNEL = "_paged_call_eva"


def _bound(fam, mc, rec, peaks) -> float:
    return rec["steps"] * max(
        fam.eva_decode_kernel_bytes(mc, rec["kv_attended_rows"])
        / peaks["hbm_bytes_per_s"],
        fam.eva_decode_kernel_ops(mc, rec["kv_attended_rows"])
        / peaks["bf16_flops_per_s"])


def read(ctx):
    return bound_share(ctx, KERNEL, _bound)
