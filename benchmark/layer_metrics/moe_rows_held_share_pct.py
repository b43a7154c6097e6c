"""Model step: of the token-expert rows the router routed over the
window, the share that fell to experts whose matrices this chip holds
(``/stats.moe``: ``rows`` over ``valid_rows``).  A chip that holds 64 of
256 experts under a router that spreads evenly reads 25; the other rows
(``rows_absent``) are the other chips' of the deployment and are left
out.  ``None`` from a program that holds every expert (no ``rows_absent``
in its ``moe`` section) or has none."""
from layer_metrics import delta


def read(ctx):
    if "rows_absent" not in ctx["stats_close"].get("moe", {}):
        return None
    routed = delta(ctx, "moe", "valid_rows")
    if not routed:
        return None
    return 100.0 * delta(ctx, "moe", "rows") / routed
