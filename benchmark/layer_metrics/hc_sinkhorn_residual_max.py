"""Model step: how far the served doubly-stochastic stream maps stand from
1: the largest ``|row or column sum - 1|`` the program read from its own
kernel at start-up (``/stats.hc.sinkhorn_residual_max`` at the window's
close: the first block's attention map over 128 token rows, the served
leaves and iteration count).  ~1e-6 after the configuration's 20 Sinkhorn
steps in float32; 5e-3 with maps in bfloat16, 0.1-0.2 after one step, which
the canary's log-probabilities cannot tell apart.  The family's reference
check refuses a run over its ``HC_RESIDUAL_LIMIT``.  ``None`` from a
program without the reading."""


def read(ctx):
    try:
        return ctx["stats_close"]["hc"]["sinkhorn_residual_max"]
    except (KeyError, TypeError):
        return None
