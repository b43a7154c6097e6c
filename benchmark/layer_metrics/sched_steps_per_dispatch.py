"""Scheduler: decode steps the device ran per host dispatch, over the
window (``--decode-block`` when fusion engages on every dispatch)."""
from layer_metrics import delta


def read(ctx):
    steps = delta(ctx, "device_loop", "device_loop_steps")
    disp = delta(ctx, "device_loop", "host_dispatches")
    return steps / disp if steps is not None and disp else None
