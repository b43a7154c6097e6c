"""Kernels: the state-space step's share of its roofline where the heads go
in GROUPS (``_ssd_step.<n>``: one call an ``M`` block a decode step, a head
reads its group's B and C): ``ssd_decode_kernel_roofline_pct``'s reading,
unchanged, through this cell's family (``ssd_decode_kernel_ops`` /
``_bytes`` at ``[64, 64, 128]`` a row a block).  ``None`` without the call,
the records or the column."""
from layer_metrics.ssd_decode_kernel_roofline_pct import read  # noqa: F401
