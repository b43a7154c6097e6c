"""Model step: median device time of the executions that carried no
prefill segment (``segments == 0`` in the dispatch record each was joined
to, ``dispatch_join.py``); ``None`` under 5 of them."""
from dispatch_join import step_ms_p50


def read(ctx):
    return step_ms_p50(ctx, prefill=False)
