"""Scheduler: share of the mixed dispatches' token budget that carried
tokens, over the window.  ``/stats.mixed.budget_utilization`` is packed
over offered tokens since start; every dispatch offers the same budget, so
the window's share follows from the two snapshots."""


def read(ctx):
    a, b = ctx["stats_open"].get("mixed"), ctx["stats_close"].get("mixed")
    if not a or not b or b["dispatches"] == a["dispatches"]:
        return None
    ua, ub = a["budget_utilization"] or 0.0, b["budget_utilization"] or 0.0
    return 100.0 * ((ub * b["dispatches"] - ua * a["dispatches"])
                    / (b["dispatches"] - a["dispatches"]))
