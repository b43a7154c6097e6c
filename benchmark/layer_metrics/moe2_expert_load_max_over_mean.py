"""Model step: how uneven the routing was over the window in a cell whose
experts are of two matrices: the rows each HELD expert got
(``/stats.moe.expert_rows``, summed over the blocks that have experts and
over executions), fullest over the mean
(``moe_expert_load_max_over_mean``'s reading, unchanged).  Beside
``moe2_experts_touched_pct`` it says whether the seeded router spreads a
step's 192 held rows as a deployment's balanced one would (an even draw
over 64 experts: about 95 % touched, fullest over mean about 2.5 a step and
toward 1 over a window).  ``None`` for a program without a ``moe``
section."""
from layer_metrics.moe_expert_load_max_over_mean import read  # noqa: F401
