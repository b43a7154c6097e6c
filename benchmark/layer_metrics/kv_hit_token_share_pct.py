"""KV manager: prompt tokens served from cached pages over the prompt
tokens the client sent, both inside the window."""
from layer_metrics import delta


def read(ctx):
    t_open, t_close = ctx["window"]
    sent = sum(r.prompt_len for r in ctx["records"]
               if t_open <= r.sent < t_close)
    hit = delta(ctx, "kvcache", "partial_hit_tokens")
    return 100.0 * hit / sent if hit is not None and sent else None
