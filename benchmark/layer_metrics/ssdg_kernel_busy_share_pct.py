"""Kernels: device time inside the two state-space calls over the device's
busy time (``ssd_kernel_busy_share_pct``'s reading) in a cell whose ``M``
blocks are four of nine.  ``None`` where the trace holds neither call."""
from layer_metrics.ssd_kernel_busy_share_pct import read  # noqa: F401
