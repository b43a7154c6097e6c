"""Kernels: the recurrent step's share of its roofline (``_kda_step.<n>``
in the trace: one call a kda block a decode step, every decoding row's
state read, decayed, corrected and written in place), as
``mla_decode_kernel_roofline_pct`` reads the latent calls'.  Operations
and bytes come from the matched records' ``kda_row_steps`` (rows x steps
that advanced a state) through the family's ``kda_decode_kernel_ops`` /
``kda_decode_kernel_bytes``, which count the recurrence's own work (the
state once in and once out a row a block a step, and the row's vectors),
whatever form implements it: the share reads under, never over.  ``None``
without the call in the trace, the join or the column."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share

KERNEL = "_kda_step"


def _bound(fam, mc, rec, peaks) -> float:
    return max(
        fam.kda_decode_kernel_bytes(mc, rec["kda_row_steps"])
        / peaks["hbm_bytes_per_s"],
        fam.kda_decode_kernel_ops(mc, rec["kda_row_steps"])
        / peaks["bf16_flops_per_s"])


def read(ctx):
    return bound_share(ctx, KERNEL, _bound)
