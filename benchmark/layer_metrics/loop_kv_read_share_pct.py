"""Model step of a looped model: of the bytes the window's decode steps
had to read, the share that was keys and values.  KV: the growth of
``/stats.dispatch_trace.kv_token_steps`` (tokens held by the decoding
rows, summed over the steps) x the bytes a token holds in all its planes;
weights: the decode steps (``/stats.loop.decode_passes`` / ``ut_steps``)
x the family's ``decode_step_bytes`` with no token.  A pass keeps its own
keys and values, so this share is what sharing them between passes would
cut; recorded as lower-is-better for that reason.  ``None`` for a
one-pass model (no ``loop`` section)."""
from layer_metrics import delta
from layer_metrics.loop_pass_hbm_pct import step_bytes


def read(ctx):
    count = step_bytes(ctx)
    passes = delta(ctx, "loop", "decode_passes")
    token_steps = delta(ctx, "dispatch_trace", "kv_token_steps")
    if count is None or not passes or token_steps is None:
        return None
    steps = passes / ctx["stats_close"]["loop"]["ut_steps"]
    weights = steps * count(0)
    keys_values = count(token_steps) - count(0)
    return 100.0 * keys_values / (keys_values + weights)
