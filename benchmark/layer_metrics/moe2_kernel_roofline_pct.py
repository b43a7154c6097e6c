"""Kernels: the grouped-matmul calls' share of their roofline in a cell
whose experts are of TWO matrices (``moe_gmm.<n>`` in the trace: an up and a
down call an ``E`` block a pass).  As ``moe_kernel_roofline_pct`` reads the
gated experts' three calls, with the records picked as the accepted
``ssd_decode_kernel_roofline_pct.span_share`` picks them (the join's pairs,
or the trace's span where a device that is never idle fails the join).  Operations
and bytes come from the records' ``moe_rows`` and ``moe_touched`` through
the family's ``moe_kernel_ops`` / ``moe_kernel_bytes`` (this cell's: ``2 x 2
x rows x H x I``; two matrices a touched expert at the published width and
each row in and out of both), fixed before any reading.  ``None`` without
the calls, the records or the columns (the parent's program)."""
from layer_metrics.moe_kernel_busy_share_pct import KERNEL
from layer_metrics.ssd_decode_kernel_roofline_pct import span_share


def _bound(fam, mc, rec, peaks) -> float:
    return max(
        fam.moe_kernel_bytes(mc, rec["moe_rows"], rec["moe_touched"], 2)
        / peaks["hbm_bytes_per_s"],
        fam.moe_kernel_ops(mc, rec["moe_rows"]) / peaks["bf16_flops_per_s"])


def read(ctx):
    return span_share(ctx, KERNEL, _bound)
