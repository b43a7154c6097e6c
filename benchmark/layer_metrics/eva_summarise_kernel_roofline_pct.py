"""Kernels: the pooling calls' share of their roofline
(``_eva_summarise.<n>``), as ``mla_decode_kernel_roofline_pct`` reads the
latent calls'.  A decoding row completes a chunk one step in ``eva_chunk``
and the records do not say which, so the chunks an execution pooled are
taken as their expectation, ``steps x (active_rows + finals) /
eva_chunk``, through the family's ``eva_summarise_kernel_ops`` /
``eva_summarise_kernel_bytes``.  A call's time is mostly its sixteen grid
steps, fifteen of which move nothing, so the share is small by design: it
is here to show if the call ever grows."""
from layer_metrics.mla_decode_kernel_roofline_pct import bound_share

KERNEL = "_eva_summarise"


def _bound(fam, mc, rec, peaks) -> float:
    chunks = (rec["steps"] * (rec["active_rows"] + rec["finals"])
              / mc["eva_chunk"])
    return max(
        fam.eva_summarise_kernel_bytes(mc, chunks) / peaks["hbm_bytes_per_s"],
        fam.eva_summarise_kernel_ops(mc, chunks) / peaks["bf16_flops_per_s"])


def read(ctx):
    return bound_share(ctx, KERNEL, _bound)
