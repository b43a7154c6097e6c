"""Kernels: the sparse decode fold's share of its roofline
(``_paged_call_sparse.<n>`` in the trace: one call a sparse block a decode
step; a row's grid step a kv head walks the list of blocks its one query
keeps and copies each block's ``[64, 128]`` of K and of V from its page).
Least bytes and operations come from the records' ``sparse_decode_blocks_
kept`` and ``sparse_decode_index_rows`` (a kv head a sparse block, the
scheduler's arithmetic on the rows' positions) through the family's
``sparse_kernel_bytes`` / ``sparse_kernel_ops``: the KEPT blocks' keys and
values and the visible index rows, and their products: what any
implementation of the equations must move and do, fixed before any reading
(ISSUE 69), so a later kernel cannot read over 100 by doing less.  The
index rows are read by the selection, outside this call: counting them
here can only raise the share.  The records as ``ssd_decode_kernel_
roofline_pct`` picks them.  ``None`` without the call, the records or the
columns (the parent's program)."""
from layer_metrics.ssd_decode_kernel_roofline_pct import span_share

KERNEL = "_paged_call_sparse"


def bound(fam, mc, kept: int, rows: int, peaks) -> float:
    return max(
        fam.sparse_kernel_bytes(mc, kept, rows) / peaks["hbm_bytes_per_s"],
        fam.sparse_kernel_ops(mc, kept, rows) / peaks["bf16_flops_per_s"])


def _bound(fam, mc, rec, peaks) -> float:
    return bound(fam, mc, rec["sparse_decode_blocks_kept"],
                 rec["sparse_decode_index_rows"], peaks)


def read(ctx):
    return span_share(ctx, KERNEL, _bound)
