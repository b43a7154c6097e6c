"""Model step of a looped model: the decode step's share of its HBM
roofline.  Over the matched executions that carried no prefill segment:
the least bytes their decode steps had to read, ``steps`` x
``decode_step_bytes(mc, kv_tokens)`` (the family's own count: the layer
stack once a pass, the head once, and the keys and values of the tokens
the decoding rows held, in every plane), over their device time and the
chip's published bandwidth.  The bytes count tokens where the kernel
reads whole pages and every matrix once a pass where the program may
read more, so the share cannot pass 100.  ``None`` where the family has
no ``decode_step_bytes``, the records no ``ut_passes`` (a one-pass
model, the parent's program) or the join no such execution."""
import families
from dispatch_join import join
from layer_metrics.decode_kernel_hbm_pct import kv_element_bytes
from peaks import peaks_for


def step_bytes(ctx):
    """``kv_tokens -> bytes`` of one decode step of the run's
    configuration on a chip, or ``None`` where its family counts none."""
    conf = ctx["config"]
    mc = conf["model_config"]
    count = getattr(families.load(mc["family"]), "decode_step_bytes", None)
    if count is None:
        return None
    weight = 1 if conf["serve_model"].endswith("-int8") else 2
    kv = kv_element_bytes(conf["serve_flags"])
    return lambda kv_tokens: count(mc, kv_tokens, weight, kv,
                                   ctx["cell"]["chips"])


def read(ctx):
    count = step_bytes(ctx)
    alone = [(d, r) for _, d, r in join(ctx)["pairs"]
             if r["segments"] == 0 and r["steps"] > 0 and "ut_passes" in r]
    if count is None or not alone:
        return None
    least = sum(r["steps"] * count(r["kv_tokens"]) for _, r in alone)
    bw = peaks_for(ctx["health"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (sum(d for d, _ in alone) / 1e9 * bw)
