"""Model step: of the bytes the window's decode steps had to move, the
share that was recurrent state (``ssd_state_stream_share_pct``'s reading
through this cell's family: the state in and out, four ``M`` blocks;
weights a pass over the matrices as cut; pages of the one attention
block).  ``None`` without the counters."""
from layer_metrics.ssd_state_stream_share_pct import read  # noqa: F401
