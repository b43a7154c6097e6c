"""Kernels: the linear-attention chunk form's share of its roofline
(``_la_chunk.<n>`` in the trace: ``ops.ssd``'s chunk call with B and C a
head's own, one call a linear block a packed segment).  Operations and
bytes come from the records' ``lightning_chunk_tokens`` and ``segments``
through the family's ``la_prefill_kernel_ops`` / ``_bytes``: ``2 Q N + 2 Q
P + 4 N P`` a head a token at a chunk of 256 (every head has its own ``k
q^T``) and the state once in and once out a segment a block through HBM.
``None`` without the call, the records or the columns."""
from layer_metrics.ssd_decode_kernel_roofline_pct import span_share

KERNEL = "_la_chunk"


def _bound(fam, mc, rec, peaks) -> float:
    tokens, segments = rec["lightning_chunk_tokens"], rec["segments"]
    return max(
        fam.la_prefill_kernel_bytes(mc, tokens, segments)
        / peaks["hbm_bytes_per_s"],
        fam.la_prefill_kernel_ops(mc, tokens) / peaks["bf16_flops_per_s"])


def read(ctx):
    return span_share(ctx, KERNEL, _bound)
