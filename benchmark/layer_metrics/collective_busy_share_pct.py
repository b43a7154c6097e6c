"""Device, several chips: time in collective operations over the
device's busy time."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr.get("chips", 1) < 2 or not tr.get("op_self_total_s"):
        return None
    inside = sum(t for name, t in tr["op_self_s"]
                 if name.startswith(COLLECTIVES))
    return 100.0 * inside / tr["op_self_total_s"]
