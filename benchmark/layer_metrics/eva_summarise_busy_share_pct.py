"""Kernels: device time inside the pooling calls (``_eva_summarise.<n>``
in the trace: a decode step's completed chunks pooled from the cached
rows into the pending summary page, one call a layer a step) over the
device's busy time.  A prefill chunk's sixteen summaries are pooled from
the chunk's own keys by fused XLA ops inside the slab, which the reducer
cannot name and this share leaves out.  ``None`` where the trace holds no
such call."""
from layer_metrics.mla_decode_kernel_roofline_pct import kernel_seconds

KERNEL = "_eva_summarise"


def read(ctx):
    tr = ctx["trace"]
    inside = kernel_seconds(tr, KERNEL)
    if not inside or not tr.get("op_self_total_s"):
        return None
    return 100.0 * inside / tr["op_self_total_s"]
