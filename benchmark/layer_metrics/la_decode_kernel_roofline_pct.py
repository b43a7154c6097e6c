"""Kernels: the linear-attention step's share of its roofline
(``_la_step.<n>`` in the trace: ``ops.ssd``'s step call with B and C a
head's own, one call a linear block a decode step, every decoding row's
``[32, 128, 128]`` float32 state read, decayed, updated and written in
place).  Operations and bytes come from the records' ``lightning_row_
steps`` through the family's ``la_decode_kernel_ops`` / ``_bytes`` (the
state once in and once out a row a block a step, and the row's vectors),
fixed before any reading.  The records as ``ssd_decode_kernel_roofline_
pct`` picks them.  ``None`` without the call, the records or the column."""
from layer_metrics.ssd_decode_kernel_roofline_pct import span_share

KERNEL = "_la_step"


def _bound(fam, mc, rec, peaks) -> float:
    steps = rec["lightning_row_steps"]
    return max(
        fam.la_decode_kernel_bytes(mc, steps) / peaks["hbm_bytes_per_s"],
        fam.la_decode_kernel_ops(mc, steps) / peaks["bf16_flops_per_s"])


def read(ctx):
    return span_share(ctx, KERNEL, _bound)
