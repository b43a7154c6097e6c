"""Kernels: device time inside the linear kind's two calls (``_la_step.<n>``
and ``_la_chunk.<n>`` in the trace) over the device's busy time.  What XLA
does around them (the projections, the norms, rope, the transposes in and
out of the calls, the gate) has fused names the reducer cannot tell apart
and is left out.  ``None`` where the trace holds neither call."""
from layer_metrics.mla_decode_kernel_roofline_pct import kernel_seconds


def read(ctx):
    tr = ctx["trace"]
    inside = kernel_seconds(tr, "_la_step") + kernel_seconds(tr, "_la_chunk")
    if not inside or not tr.get("op_self_total_s"):
        return None
    return 100.0 * inside / tr["op_self_total_s"]
