"""Kernels: the residual streams' READ call's share of its roofline
(``_hc_pre_call.<n>`` in the trace: one call a sublayer, the token's ``n``
streams in, the row its norm reads and the token's coefficients out), as
``mla_decode_kernel_roofline_pct`` reads the latent calls'.  Operations and
bytes come from the matched records' ``hc_rows`` (the slab's rows and every
slot of every decode step: the rows the call computed) through the family's
``hc_pre_kernel_ops`` / ``hc_pre_kernel_bytes``, which count what the call
moves (the streams once in, one row out; the maps' weights and the
coefficients are not counted).  ``None`` without the call in the trace, the
join's pairs, the records' column or the family's functions."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share

KERNEL = "_hc_pre_call"


def _bound(fam, mc, rec, peaks) -> float:
    return max(
        fam.hc_pre_kernel_bytes(mc, rec["hc_rows"]) / peaks["hbm_bytes_per_s"],
        fam.hc_pre_kernel_ops(mc, rec["hc_rows"]) / peaks["bf16_flops_per_s"])


def read(ctx):
    return bound_share(ctx, KERNEL, _bound)
