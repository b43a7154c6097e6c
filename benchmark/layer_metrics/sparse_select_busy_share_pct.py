"""Kernels: device time inside the selection's score call
(``_sparse_scores.<n>`` in the trace: one call a sparse block a decode step
and one a segment of a slab: the queries' scores over the row's pooled
keys, their softmax a head and the group's sum) over the device's busy
time.  What XLA does around it (the gather of the index rows, the max over
the kernels that meet a block, the top-k, the lists' compaction) has fused
names the reducer cannot tell apart and is left out.  ``None`` where the
trace holds no such call."""
from layer_metrics.mla_decode_kernel_roofline_pct import kernel_seconds

KERNEL = "_sparse_scores"


def read(ctx):
    tr = ctx["trace"]
    inside = kernel_seconds(tr, KERNEL)
    if not inside or not tr.get("op_self_total_s"):
        return None
    return 100.0 * inside / tr["op_self_total_s"]
