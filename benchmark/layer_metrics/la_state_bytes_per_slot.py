"""KV manager: the bytes one request holds in the state pool whatever its
length (``/stats.kvcache.kinds.state.bytes_per_slot``) for a configuration
whose state kind is the linear one: six blocks of ``32 x 128 x 128``
float32 = 12,582,912, and two bytes a block that stand where another state
kind keeps a convolution's tail.  A state kept in bfloat16 reads half.
``None`` from a program without the counter, and for a family of another
state kind."""
import families


def read(ctx):
    fam = families.load(ctx["config"]["model_config"]["family"])
    if not hasattr(fam, "la_state_bytes_per_slot"):
        return None
    return (ctx["stats_close"].get("kvcache", {}).get("kinds", {})
            .get("state", {}).get("bytes_per_slot"))
