"""Kernels: the chunk form's share of its roofline where the heads go in
GROUPS (``_ssd_chunk.<n>``: one call an ``M`` block a packed segment, a
head block of one group a grid step with that group's ``B C^T``): the
family's count alone (``ssd_prefill_kernel_ops`` at the kind's chunk: ``2 Q
N`` a group + ``2 Q P + 4 N P`` a head; the state once in and out a segment
a block; ISSUE 62's older count, which the accepted reader prints beside
its family's, is not this family's), through the accepted readers'
``span_share`` and ``_bound``, unchanged.  ``None`` without the call, the
records or the columns."""
from layer_metrics.ssd_decode_kernel_roofline_pct import span_share
from layer_metrics.ssd_prefill_kernel_roofline_pct import KERNEL, _bound


def read(ctx):
    return span_share(ctx, KERNEL, _bound)
