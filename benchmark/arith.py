"""The arithmetic from client records to numbers.  No I/O, no clock."""

from __future__ import annotations

import math

MIN_BEYOND = 10     # a percentile needs this many samples beyond it


def percentile(values, q: float, min_beyond: int = MIN_BEYOND):
    """The ``q``-th percentile (linear interpolation between ranks), or
    ``None`` where fewer than ``min_beyond`` samples lie beyond it on the
    far side: a 95th percentile of a dozen values is a maximum."""
    xs = sorted(values)
    n = len(xs)
    far = n * (1 - q / 100.0) if q >= 50 else n * q / 100.0
    if n == 0 or far + 1e-9 < min_beyond:
        return None
    pos = (n - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, n - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    """The median of any non-empty sample (no minimum count)."""
    return percentile(values, 50, min_beyond=0)


def ttft_ms(rec) -> float:
    """Milliseconds from the instant the request was DUE to its first
    token: in an open loop a stall delays every request behind it, and
    that wait belongs to the server, not to the generator."""
    return (rec.token_times[0] - rec.due) * 1e3


def tpot_ms(rec):
    """(last token - first token) / (tokens - 1) of one request, in ms.
    Per request and not per gap: a fused decode block delivers several
    tokens at once, so single gaps are 0 or a whole block."""
    n = len(rec.token_times)
    if n < 2:
        return None
    return (rec.token_times[-1] - rec.token_times[0]) / (n - 1) * 1e3


def late_ms(rec) -> float:
    """How late the generator sent the request."""
    return (rec.sent - rec.due) * 1e3


def tokens_in_window(records, t_open: float, t_close: float) -> int:
    """Output tokens that arrived inside the window, over the given
    (well-formed) records, whenever their requests were due."""
    return sum(1 for r in records for t in r.token_times
               if t_open <= t < t_close)


def spread(values) -> float:
    """Distance between the quartiles over the median (the driver's
    measure of run-to-run spread)."""
    q1 = percentile(values, 25, min_beyond=0)
    q3 = percentile(values, 75, min_beyond=0)
    return (q3 - q1) / median(values)
