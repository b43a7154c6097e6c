"""Kernels: device time inside the residual streams' two calls
(``_hc_pre_call.<n>`` and ``_hc_post_call.<n>`` in the trace) over the
device's busy time: what four streams a token cost beside the blocks'
matmuls.  ``None`` where the trace holds neither call."""
from layer_metrics.mla_decode_kernel_roofline_pct import kernel_seconds


def read(ctx):
    tr = ctx["trace"]
    inside = (kernel_seconds(tr, "_hc_pre_call")
              + kernel_seconds(tr, "_hc_post_call"))
    if not inside or not (tr or {}).get("op_self_total_s"):
        return None
    return 100.0 * inside / tr["op_self_total_s"]
