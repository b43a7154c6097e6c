"""Expert layer: of the experts a layer call could have touched, the share
that got at least one row, over the window.  ``/stats.moe.touched`` sums,
over every layer call of every execution, the experts with >= 1 row;
``layer_calls`` counts the calls and ``experts`` is their number.  Near
100 in a prefill slab, lower in a decode step over few rows; the weight
stream of a pass (``step_weight_stream_pct``) is taken to read every
expert, and this says how far that holds.  ``None`` for a model without
experts (no ``moe`` section)."""
from layer_metrics import delta


def read(ctx):
    touched = delta(ctx, "moe", "touched")
    calls = delta(ctx, "moe", "layer_calls")
    if not calls:
        return None
    return 100.0 * touched / (calls * ctx["stats_close"]["moe"]["experts"])
