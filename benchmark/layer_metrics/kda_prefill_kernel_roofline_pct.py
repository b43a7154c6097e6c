"""Kernels: the chunk form's share of its roofline (``_kda_chunk.<n>`` in
the trace: one call a kda block a packed segment, the pass over the
segment's chunks that reads and writes the state).  Operations and bytes
come from the matched records' ``kda_chunk_tokens`` and ``segments``
through the family's ``kda_prefill_kernel_ops`` /
``kda_prefill_kernel_bytes``: the recurrence's own operations a token,
the state once in and once out a segment a block, the tokens' vectors.
The call multiplies float32 matrices at full precision (several passes of
the unit whose published rate the bound divides by) and XLA builds the
chunks' matrices outside it, so the share is small by design and reads
under, never over.  ``None`` without the call, the join or the columns."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share

KERNEL = "_kda_chunk"


def _bound(fam, mc, rec, peaks) -> float:
    tokens, segments = rec["kda_chunk_tokens"], rec["segments"]
    return max(
        fam.kda_prefill_kernel_bytes(mc, tokens, segments)
        / peaks["hbm_bytes_per_s"],
        fam.kda_prefill_kernel_ops(mc, tokens) / peaks["bf16_flops_per_s"])


def read(ctx):
    return bound_share(ctx, KERNEL, _bound)
