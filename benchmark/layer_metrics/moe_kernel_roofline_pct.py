"""Kernels: the grouped-matmul calls' share of their roofline.  Over the
executions the join matched: the larger of (least bytes / peak HBM
bandwidth) and (operations / peak bf16 rate), over the calls' own time in
the trace scaled by matched / all executions.  Operations and bytes come
from the program's routing counters in the matched dispatch records
(``moe_rows``: token-expert rows; ``moe_touched``: (layer call, expert)
pairs with at least one row) through the family's own functions
(``families/<family>.py``: ``moe_kernel_ops``, ``moe_kernel_bytes``), so
bytes count what was touched, never every expert.  The bound is taken
per execution and summed: a slab may be bound by the MXU where a decode
step is bound by HBM.  ``None`` without the kernel in the trace, the
counters in the records, or the family's functions."""
import families
from dispatch_join import join
from layer_metrics.moe_kernel_busy_share_pct import kernel_seconds
from peaks import peaks_for


def read(ctx):
    j, tr = join(ctx), ctx["trace"]
    kernel_s = kernel_seconds(tr)
    if not j["pairs"] or not kernel_s:
        return None
    mc = ctx["config"]["model_config"]
    fam = families.load(mc["family"])
    if not hasattr(fam, "moe_kernel_ops"):
        return None
    quant = 1 if ctx["config"]["serve_model"].endswith("-int8") else 2
    peaks = peaks_for(ctx["health"]["device_kind"])
    bound_s = 0.0
    for _, _, rec in j["pairs"]:
        if "moe_rows" not in rec:
            return None
        bound_s += max(
            fam.moe_kernel_bytes(mc, rec["moe_rows"], rec["moe_touched"],
                                 quant) / peaks["hbm_bytes_per_s"],
            fam.moe_kernel_ops(mc, rec["moe_rows"])
            / peaks["bf16_flops_per_s"])
    return 100.0 * bound_s / (kernel_s * j["share"])
