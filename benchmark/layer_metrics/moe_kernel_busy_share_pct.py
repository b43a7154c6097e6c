"""Kernels: device time inside the grouped-matmul Pallas calls (the
experts' gate, up and down projections) over the device's busy time.  In
the trace they are the custom calls ``moe_gmm.<n>``.  ``None`` where the
trace holds none (a model without experts, or a program before the
kernel)."""

KERNEL = "moe_gmm"


def kernel_seconds(trace) -> float:
    """Own time of the grouped-matmul calls (a chip's mean)."""
    return sum(t for name, t in (trace or {}).get("op_self_s", [])
               if name.startswith(KERNEL))


def read(ctx):
    tr = ctx["trace"]
    inside = kernel_seconds(tr)
    if not inside or not tr.get("op_self_total_s"):
        return None
    return 100.0 * inside / tr["op_self_total_s"]
