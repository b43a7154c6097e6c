"""Model step: of the token-expert rows the router routed over the window,
the share that fell to experts this chip holds
(``moe_rows_held_share_pct``'s reading: ``/stats.moe`` ``rows`` over
``valid_rows``, both counted over the blocks that have experts).  64 of 128
held under a router that spreads evenly reads about 50.  ``None`` from a
program that holds every expert or has none."""
from layer_metrics.moe_rows_held_share_pct import read  # noqa: F401
