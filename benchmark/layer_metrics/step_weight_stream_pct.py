"""Model step: the share of peak HBM bandwidth that the weight stream
alone explains.  Every execution reads the weights once for its prefill
slab and once for each decode step it fused; those bytes (from the
configuration's shapes, ``bytes.py``) over the executions' device time
and the chip's published bandwidth.  A floor under the step's roofline
share, not a kernel's roofline."""
import importlib

from layer_metrics import delta
from layer_metrics.step_ms_p50 import step_durations_ns
from peaks import peaks_for

_bytes = importlib.import_module("bytes")      # benchmark/bytes.py


def read(ctx):
    durs = step_durations_ns(ctx)
    steps = delta(ctx, "device_loop", "device_loop_steps",
                  "stats_trace_start", "stats_trace_stop")
    if not durs or steps is None:
        return None
    span = ctx["marks"]
    wall = (span["trace_stopped"]["stop"]["monotonic"]
            - span["trace_started"]["running"]["monotonic"])
    counted = span["stats_trace_stop_at"] - span["stats_trace_start_at"]
    quant = "int8" if ctx["config"]["serve_model"].endswith("-int8") \
        else "none"
    per_pass = _bytes.weight_bytes_per_pass(
        ctx["config"]["model_config"], quant, ctx["cell"]["chips"])
    # executions and steps are counted by the program between two /stats
    # reads that bracket the trace a little loosely; scale both to it
    passes = (len(durs) + steps * wall / counted)
    bw = peaks_for(ctx["health"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * passes * per_pass / (sum(durs) / 1e9 * bw)
