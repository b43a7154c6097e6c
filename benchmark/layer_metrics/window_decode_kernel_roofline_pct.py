"""Kernels: the window kind's decode calls' share of their roofline
(``_paged_call_window.<n>`` in the trace: the paged decode kernel under a
window bound, which visits only the pages a row's last ``W`` tokens
meet), as ``mla_decode_kernel_roofline_pct`` reads the latent calls'.
Operations and bytes come from the matched records' ``kv_window_tokens``
(the sum over the rows that decoded of min(tokens held, window)) and
``steps`` through the family's ``window_decode_kernel_ops`` /
``window_decode_kernel_bytes``.  Bytes count tokens where the kernel
reads whole pages, and a query tile padded from 9 heads to 16 rows is not
work, so the share reads under, never over.  ``None`` without the kernel
in the trace, the column in the records, or the family's functions."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share

KERNEL = "_paged_call_window"


def _bound(fam, mc, rec, peaks) -> float:
    return rec["steps"] * max(
        fam.window_decode_kernel_bytes(mc, rec["kv_window_tokens"])
        / peaks["hbm_bytes_per_s"],
        fam.window_decode_kernel_ops(mc, rec["kv_window_tokens"])
        / peaks["bf16_flops_per_s"])


def read(ctx):
    return bound_share(ctx, KERNEL, _bound)
