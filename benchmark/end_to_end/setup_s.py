"""Process start to window open: children, weights, warm-up (compiling on
a first run), reference check, canaries and the unmeasured ramp."""


def read(ctx):
    return ctx["seconds_before_window"]
