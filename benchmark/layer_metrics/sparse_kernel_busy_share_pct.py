"""Kernels: device time inside the two sparse folds
(``_paged_call_sparse.<n>`` and ``_paged_prefill_call_sparse.<n>`` in the
trace) over the device's busy time.  ``None`` where the trace holds
neither call."""
from layer_metrics.mla_decode_kernel_roofline_pct import kernel_seconds


def read(ctx):
    tr = ctx["trace"]
    inside = (kernel_seconds(tr, "_paged_call_sparse")
              + kernel_seconds(tr, "_paged_prefill_call_sparse"))
    if not inside or not tr.get("op_self_total_s"):
        return None
    return 100.0 * inside / tr["op_self_total_s"]
