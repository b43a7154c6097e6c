"""Kernels: the state-space step's share of its roofline (``_ssd_step.<n>``
in the trace: one call an ssd block a decode step, every decoding row's
state read, decayed, updated and written in place), as
``kda_decode_kernel_roofline_pct`` reads the other state kind's.
Operations and bytes come from the records' ``ssd_row_steps`` (rows x steps
that advanced a state: the call moves a live row's block of the pool and no
dead row's, ``ops.ssd._blocks_of``) through the family's
``ssd_decode_kernel_ops`` / ``ssd_decode_kernel_bytes``, which count the
recurrence's dense work (the state once in and once out a row a block a
step, and the row's vectors) and were fixed before any reading: the share
reads under, never over.

Which records: those ``dispatch_join`` matched, where its join holds.  A
device that is never idle opens a trace inside an execution, and the join
then fails its own order check (PERF.md section 7); :func:`span_share`
reads such a run by the records whose ``t_done`` lies in the trace's span
on the join's own offset: every execution but the one the trace's end cut,
against the calls' whole time in the trace, the cut pieces of the first and
the last execution included (together about one execution of the sixty an
8 s trace of this cell holds; the ``[ssd]`` line says which way it was
read).  ``None`` without the call in the trace, the records or the column
(the parent's program)."""
import families
from dispatch_join import join
from layer_metrics.mla_decode_kernel_roofline_pct import (bound_share,
                                                          kernel_seconds)
from peaks import peaks_for

KERNEL = "_ssd_step"


def span_share(ctx, prefix: str, bound_of):
    """``bound_share`` where the join holds; else 100 x the summed bound of
    the records that ended inside the trace over the calls' own time."""
    got = bound_share(ctx, prefix, bound_of)
    j = join(ctx)
    kernel_s = kernel_seconds(ctx["trace"], prefix)
    if got is not None or j["pairs"] or not kernel_s or j["offset"] is None:
        return got
    execs = j["executions"]
    lo = j["offset"] + execs[0][0] / 1e9
    hi = j["offset"] + (execs[-1][0] + execs[-1][1]) / 1e9
    inside = [r for r in j["records"] if lo < r["t_done"] <= hi]
    mc = ctx["config"]["model_config"]
    fam = families.load(mc["family"])
    peaks = peaks_for(ctx["health"]["device_kind"])
    try:
        bound_s = sum(bound_of(fam, mc, r, peaks) for r in inside)
    except (AttributeError, KeyError):      # another family, or no column
        return None
    print(f"[ssd] {prefix}: the join gave no pairs; read by the "
          f"{len(inside)} records that ended inside the trace's span "
          f"({len(execs)} executions, the first and the last cut), bound "
          f"{bound_s * 1e3:.3f} ms over {kernel_s * 1e3:.3f} ms", flush=True)
    return 100.0 * bound_s / kernel_s if inside else None


def _bound(fam, mc, rec, peaks) -> float:
    return max(
        fam.ssd_decode_kernel_bytes(mc, rec["ssd_row_steps"])
        / peaks["hbm_bytes_per_s"],
        fam.ssd_decode_kernel_ops(mc, rec["ssd_row_steps"])
        / peaks["bf16_flops_per_s"])


def read(ctx):
    return span_share(ctx, KERNEL, _bound)
