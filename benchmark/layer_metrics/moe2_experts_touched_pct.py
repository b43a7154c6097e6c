"""Model step: of the held experts a layer call could have touched, the
share that got at least one row, over the window
(``moe_experts_touched_pct``'s reading).  ``/stats.moe.layer_calls`` counts
the calls of the blocks that HAVE experts (four of nine here), so the share
is of those.  ``None`` for a program without a ``moe`` section."""
from layer_metrics.moe_experts_touched_pct import read  # noqa: F401
