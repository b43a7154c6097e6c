"""Model step: of the bytes the window's decode steps had to move, the
share that was recurrent state.  State: the growth of
``/stats.kvcache.kinds.state.row_steps`` (rows x steps that advanced a
state) x the family's ``kda_decode_kernel_bytes`` of one (the state in
and out, every kda block); weights: the decode steps
(``/stats.device_loop.device_loop_steps``) x the bytes of one pass over
the matrices as cut (``bytes.weight_bytes_per_pass``: every held expert,
where a step touches most of them); pages: the growth of
``/stats.dispatch_trace.kv_token_steps`` x the bytes a token holds in the
full kind's planes.  The state grows with the rows where the weights do
not: this share is what a wider batch raises.  ``None`` without the
counters (another family, the parent's program)."""
import importlib

import families
from layer_metrics import delta

_bytes = importlib.import_module("bytes")      # benchmark/bytes.py


def read(ctx):
    conf = ctx["config"]
    mc = conf["model_config"]
    fam = families.load(mc["family"])
    count = getattr(fam, "kda_decode_kernel_bytes", None)
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
