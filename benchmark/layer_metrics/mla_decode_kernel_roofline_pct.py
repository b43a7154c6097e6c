"""Kernels: the latent decode calls' share of their roofline.  Over the
executions the join matched: the larger of (least bytes / peak HBM
bandwidth) and (operations / peak bf16 rate), over the calls' own time in
the trace (``_paged_call_latent.<n>``) scaled by matched / all executions.
Operations and bytes come from the matched dispatch records (``kv_tokens``:
tokens held by the rows that decoded; ``steps``: the decode steps the
device ran) through the family's own functions (``families/<family>.py``:
``mla_decode_kernel_ops``, ``mla_decode_kernel_bytes``).  Bytes count the
tokens held where the kernel reads whole pages, so the share reads under,
never over.  The bound is taken per execution and summed.  ``None``
without the kernel in the trace, the join, or the family's functions."""
import families
from dispatch_join import join
from peaks import peaks_for

KERNEL = "_paged_call_latent"


def kernel_seconds(trace, prefix: str) -> float:
    """Own time of the calls whose name starts with ``prefix``."""
    return sum(t for name, t in (trace or {}).get("op_self_s", [])
               if name.startswith(prefix))


def bound_share(ctx, prefix: str, bound_of) -> float | None:
    """100 x the summed ``bound_of(family, model_config, record, peaks)``
    seconds of the matched executions over the own time of the calls
    named ``prefix...``; ``None`` where there is nothing to read."""
    j, tr = join(ctx), ctx["trace"]
    kernel_s = kernel_seconds(tr, prefix)
    if not j["pairs"] or not kernel_s:
        return None
    mc = ctx["config"]["model_config"]
    fam = families.load(mc["family"])
    peaks = peaks_for(ctx["health"]["device_kind"])
    try:
        bound_s = sum(bound_of(fam, mc, rec, peaks) for _, _, rec in j["pairs"])
    except (AttributeError, KeyError):     # another family, or no column
        return None
    return 100.0 * bound_s / (kernel_s * j["share"])


def _bound(fam, mc, rec, peaks) -> float:
    return rec["steps"] * max(
        fam.mla_decode_kernel_bytes(mc, rec["kv_tokens"])
        / peaks["hbm_bytes_per_s"],
        fam.mla_decode_kernel_ops(mc, rec["kv_tokens"])
        / peaks["bf16_flops_per_s"])


def read(ctx):
    return bound_share(ctx, KERNEL, _bound)
