"""Model step: median device time of one execution of the serving
program (``mixed_step``), from the trace's ``XLA Modules`` line.  Today
the trace cannot tell an execution that carries a prefill chunk from one
that only decodes (both are ``jit_mixed_step``, and every execution runs
the whole prefill slab), so this is one number over both."""
from arith import median


def step_durations_ns(ctx) -> list:
    mods = ctx["trace"].get("modules", {}) if ctx["trace"] else {}
    name = ctx["config"].get("step_module", "jit_mixed_step")
    return [d for _, d in mods.get(name, [])]


def read(ctx):
    durs = step_durations_ns(ctx)
    return median(durs) / 1e6 if durs else None
