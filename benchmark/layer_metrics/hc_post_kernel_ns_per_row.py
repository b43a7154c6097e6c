"""Kernels: device time of the residual streams' WRITE call a token row a
call (``_hc_post_call.<n>`` in the trace: one call a sublayer, the ``n``
streams and the sublayer's output in, the ``n`` streams out).  The calls'
own time, scaled by matched / all executions, over the matched records'
``hc_rows`` times the calls a row passes (the family's
``hc_calls_per_row``).  A time and not a share of a roofline: in the layer
scan the compiler keeps a slab's streams in the chip's fast memory between
the calls, so HBM's bandwidth is not this call's bound and the benchmark
knows no other (the family's ``hc_post_kernel_bytes`` says what was
measured).  ``None``
without the call in the trace, the join's pairs, the records' column or the
family's function."""
import families
from dispatch_join import join
from layer_metrics.mla_decode_kernel_roofline_pct import kernel_seconds

KERNEL = "_hc_post_call"


def read(ctx):
    j = join(ctx)
    kernel_s = kernel_seconds(ctx["trace"], KERNEL)
    if not j["pairs"] or not kernel_s:
        return None
    mc = ctx["config"]["model_config"]
    try:
        row_calls = (families.load(mc["family"]).hc_calls_per_row(mc)
                     * sum(rec["hc_rows"] for _, _, rec in j["pairs"]))
    except (AttributeError, KeyError):     # another family, or no column
        return None
    if not row_calls:
        return None
    return 1e9 * kernel_s * j["share"] / row_calls
