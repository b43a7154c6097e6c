"""Kernels: the sparse prefill fold's share of its roofline
(``_paged_prefill_call_sparse.<n>`` in the trace: one call a sparse block a
slab; a grid step is a tile of 32 queries of one segment and one kv head,
and folds the blocks ANY of its queries keeps, four an iteration, under a
mask a query).  Least operations alone: the products of each query's OWN
kept blocks and its scores over the visible index rows (the records'
``sparse_blocks_kept`` / ``sparse_index_rows`` less the decoding rows'
part, through the family's ``sparse_kernel_ops``), over the matrix unit's
peak.  NO bytes' time: queries of a tile share the blocks they both keep,
so a slab's least bytes are a block once a tile that keeps it, which the
scheduler's arithmetic cannot know; counted a query (the decode reader's
count) the bytes alone read 85 % of the call's time (my chip run, PR 69),
a bound that one kernel change could pass: a slab's fold is bound by its
products at any sharing.  What the call folds beside a query's own blocks
(its tile's union, masked) is work the equations do not ask for, and it
lowers the share: with seeded weights, whose queries choose nearly
unrelated blocks, most of what a tile folds is other queries'.  ``None``
without the call, the records or the columns."""
from layer_metrics.ssd_decode_kernel_roofline_pct import span_share

KERNEL = "_paged_prefill_call_sparse"


def _bound(fam, mc, rec, peaks) -> float:
    return fam.sparse_kernel_ops(
        mc, rec["sparse_blocks_kept"] - rec["sparse_decode_blocks_kept"],
        rec["sparse_index_rows"] - rec["sparse_decode_index_rows"]
    ) / peaks["bf16_flops_per_s"]


def read(ctx):
    return span_share(ctx, KERNEL, _bound)
