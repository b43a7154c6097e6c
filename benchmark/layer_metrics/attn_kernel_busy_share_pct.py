"""Kernels: device time inside the two paged attention Pallas calls over
the device's busy time.  In the trace they are the custom calls
``_paged_call.<n>`` (decode) and ``_paged_prefill_call.<n>``."""

KERNELS = ("_paged_call", "_paged_prefill_call")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("op_self_total_s"):
        return None
    inside = sum(t for name, t in tr["op_self_s"]
                 if name.startswith(KERNELS))
    return 100.0 * inside / tr["op_self_total_s"]
