"""Kernels: the chunk form's share of its roofline (``_ssd_chunk.<n>`` in
the trace: one call an ssd block a packed segment, the segment's chunks in
order over the row's state).  Operations and bytes come from the matched
records' ``ssd_chunk_tokens`` and ``segments`` through the family's
``ssd_prefill_kernel_ops`` / ``ssd_prefill_kernel_bytes``: the products
the recurrence needs a token at the published chunk (``2 Q N`` a group +
``2 Q P + 4 N P`` a head) and the state once in and once out a segment a
block THROUGH HBM; the tokens' rows are not counted, because the compiler
keeps them in the chip's fast memory around the call (counted, the share
read 108 %: the family's docstring).  The call masks and decays a ``[Q,
Q]`` tile a head on the vector unit between its products, so the share
reads under.  The records as ``ssd_decode_kernel_roofline_pct`` picks them
(the join's pairs, or the trace's span where the join fails).  ``None``
without the call, the records or the columns."""
from layer_metrics.ssd_decode_kernel_roofline_pct import span_share

KERNEL = "_ssd_chunk"


def _bound(fam, mc, rec, peaks) -> float:
    tokens, segments = rec["ssd_chunk_tokens"], rec["segments"]
    return max(
        fam.ssd_prefill_kernel_bytes(mc, tokens, segments)
        / peaks["hbm_bytes_per_s"],
        fam.ssd_prefill_kernel_ops(mc, tokens) / peaks["bf16_flops_per_s"])


def _bound_as_issued(fam, mc, rec, peaks) -> float:
    ops, moved = fam.ssd_prefill_kernel_as_issued(
        mc, rec["ssd_chunk_tokens"], rec["segments"])
    return max(moved / peaks["hbm_bytes_per_s"],
               ops / peaks["bf16_flops_per_s"])


def read(ctx):
    got = span_share(ctx, KERNEL, _bound)
    if got is not None:
        # the count the issue fixed before any reading, beside the family's
        issued = span_share(ctx, KERNEL, _bound_as_issued)
        print(f"[ssd] {KERNEL}: {got:.1f} % by the family's count (the "
              f"state through HBM, the product a group); by ISSUE 62's "
              f"count (the tokens' rows through HBM too, the product a "
              f"head) {issued:.1f} %: over 100 says that count is too high",
              flush=True)
    return got
