"""Kernels: device time inside the two recurrent-state calls
(``_kda_step.<n>`` and ``_kda_chunk.<n>`` in the trace) over the device's
busy time.  What XLA does around them (the projections, the convolution,
the gates, the chunks' matrices, the head's norm) has fused names the
reducer cannot tell apart and is left out.  ``None`` where the trace
holds neither call."""
from layer_metrics.mla_decode_kernel_roofline_pct import kernel_seconds


def read(ctx):
    tr = ctx["trace"]
    inside = kernel_seconds(tr, "_kda_step") + kernel_seconds(tr, "_kda_chunk")
    if not inside or not tr.get("op_self_total_s"):
        return None
    return 100.0 * inside / tr["op_self_total_s"]
