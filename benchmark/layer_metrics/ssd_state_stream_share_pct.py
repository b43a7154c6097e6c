"""Model step: of the bytes the window's decode steps had to move, the
share that was recurrent state, as ``kda_state_stream_share_pct`` has it
for the other state kind.  State: the growth of
``/stats.kvcache.kinds.state.row_steps`` x the family's
``ssd_decode_kernel_bytes`` of one (the state in and out, every ssd
block); weights: the decode steps x the bytes of one pass over the
matrices as cut (every held expert); pages: the growth of
``/stats.dispatch_trace.kv_token_steps`` x the bytes a token holds in the
full kind's planes.  ``None`` without the counters (another family, the
parent's program)."""
import importlib

import families
from layer_metrics import delta

_bytes = importlib.import_module("bytes")      # benchmark/bytes.py


def read(ctx):
    mc = ctx["config"]["model_config"]
    fam = families.load(mc["family"])
    count = getattr(fam, "ssd_decode_kernel_bytes", None)
    try:
        at = lambda key: ctx[key]["kvcache"]["kinds"]["state"]["row_steps"]
        row_steps = at("stats_close") - at("stats_open")
    except (KeyError, TypeError):
        return None
    steps = delta(ctx, "device_loop", "device_loop_steps")
    token_steps = delta(ctx, "dispatch_trace", "kv_token_steps")
    if count is None or not steps or token_steps is None:
        return None
    state = count(mc, row_steps)
    weights = steps * _bytes.weight_bytes_per_pass(mc, "none",
                                                   ctx["cell"]["chips"])
    pages = token_steps * fam.kv_bytes_per_token(mc)
    return 100.0 * state / (state + weights + pages)
