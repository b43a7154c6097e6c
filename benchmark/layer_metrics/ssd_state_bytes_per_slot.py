"""KV manager: the bytes one request holds in the state pool whatever its
length, as the program's ``/stats.kvcache.kinds.state.bytes_per_slot``
says, for a configuration whose state kind is ssd.  It must read what the
family's ``ssd_state_bytes_per_slot`` says (a float32 state and the
convolution's tail an ssd block: 38,204,928 at the published widths): a
state kept in bfloat16 reads about half.  ``None`` from a program without
the counter, and for a family of another state kind."""
import families


def read(ctx):
    fam = families.load(ctx["config"]["model_config"]["family"])
    if not hasattr(fam, "ssd_state_bytes_per_slot"):
        return None
    return (ctx["stats_close"].get("kvcache", {}).get("kinds", {})
            .get("state", {}).get("bytes_per_slot"))
