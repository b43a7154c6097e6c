"""Kernels: device time inside the grouped-matmul calls over the device's
busy time, in a cell whose experts are of two matrices (two ``moe_gmm.<n>``
an ``E`` block a pass): ``moe_kernel_busy_share_pct``'s reading under the
name this cell's list carries.  ``None`` where the trace holds no such
call."""
from layer_metrics.moe_kernel_busy_share_pct import read  # noqa: F401
