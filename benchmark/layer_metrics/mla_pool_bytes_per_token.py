"""KV manager: the bytes one token holds in the page pool, every plane, as
the program's ``/stats.kvcache.bytes_per_token`` says (a page's bytes over
its tokens).  For a latent-attention model it must read what the family's
``kv_bytes_per_token`` says (one lane-padded latent row a block): a pool
that stored the latent twice, or decompressed keys and values, would show
here at once.  ``None`` from a program without the counter."""


def read(ctx):
    return ctx["stats_close"].get("kvcache", {}).get("bytes_per_token")
