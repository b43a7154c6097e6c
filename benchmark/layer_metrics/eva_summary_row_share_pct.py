"""Model step: of the rows the decoding rows' queries attended, the share
that were summaries (``kv_summary_rows`` over ``kv_attended_rows`` of the
dispatch records the run kept, each weighed by its ``steps``).  ``None``
from a program whose records have no such columns."""
from dispatch_join import records


def read(ctx):
    ctx["stats_close"]          # (a reader needs the run)
    rows = [r for r in records(ctx) if "kv_attended_rows" in r]
    attended = sum(r["kv_attended_rows"] * r["steps"] for r in rows)
    if not attended:
        return None
    return 100.0 * sum(r["kv_summary_rows"] * r["steps"]
                       for r in rows) / attended
