"""Gateway + HTTP replica: calls of ``wfile.write`` a token written
(``/stats.request_path``: ``writes`` over ``tokens``, the window's
edges).  A streamed token is one JSONL line in one HTTP chunk, and a
chunk is two unbuffered writes (its length, then the line): 2.0 by
construction until a handler writes a hand-off's tokens as one line and a
line in one write (0.25 at ``--decode-block 4``).  ``None`` where the
program has no such record."""
from request_path import per


def read(ctx):
    return per(ctx, ("writes",), "tokens")
