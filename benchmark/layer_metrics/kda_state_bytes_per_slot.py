"""KV manager: the bytes one request holds in the state pool whatever its
length, as the program's ``/stats.kvcache.kinds.state.bytes_per_slot``
says.  It must read what the family's ``kda_state_bytes_per_slot`` says (a
float32 state and the convolution's tail a kda block: 13,025,280 at the
published widths): a state kept in bfloat16 reads about half, one held
twice double.  ``None`` from a program without the counter."""


def read(ctx):
    return (ctx["stats_close"].get("kvcache", {}).get("kinds", {})
            .get("state", {}).get("bytes_per_slot"))
