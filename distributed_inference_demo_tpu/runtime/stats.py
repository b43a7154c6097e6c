"""Hot-loop observability: per-stage comm/compute timers and byte counts.

The reference accumulates per-token communication and inference time in
``commutimeArraySum`` / ``infertimeArraySum`` / ``byteArraySum``
(``Communication.java:104-107,859-896``) and prints the sums at the end of a
run (``:650-661``).  This module is the structured equivalent: every pipeline
role owns a ``StageStats``, the ring loop feeds it, and a ``snapshot()``
dict flows to the ``/stats`` HTTP endpoint and the
cross-process stats collection (header polls workers with a ``statsreq``
control message — the GET_STATUS idea applied to the data plane).

Latency percentiles come from bounded reservoirs of per-event samples, so
long runs keep O(1) memory.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque

_MAX_SAMPLES = 4096


def _percentile(samples, q: float) -> float:
    """Nearest-rank percentile: smallest x with cdf(x) >= q/100."""
    if not samples:
        return float("nan")
    xs = sorted(samples)
    idx = min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))
    return xs[idx]


class StageStats:
    """Counters + latency reservoirs for one pipeline role.

    Phases mirror the reference's OneStep timers (SURVEY.md §3.3):
    ``recv_wait`` (commu1), ``compute`` (infer), ``send`` (commu2), plus
    header-side ``ring_rtt`` (commu3: send hidden -> token back).
    """

    def __init__(self, role: str = "stage"):
        self.role = role
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.started_at = time.time()
            self.steps = 0
            self.recv_wait_s = 0.0
            self.compute_s = 0.0
            self.send_s = 0.0
            self.bytes_in = 0
            self.bytes_out = 0
            self.messages_in = 0
            self.messages_out = 0
            self._compute_samples = deque(maxlen=_MAX_SAMPLES)
            self._rtt_samples = deque(maxlen=_MAX_SAMPLES)

    # -- recording ---------------------------------------------------------

    def record_recv(self, wait_s: float, nbytes: int) -> None:
        with self._lock:
            self.recv_wait_s += wait_s
            self.bytes_in += nbytes
            self.messages_in += 1

    def record_compute(self, seconds: float) -> None:
        with self._lock:
            self.compute_s += seconds
            self.steps += 1
            self._compute_samples.append(seconds)

    def record_send(self, seconds: float, nbytes: int) -> None:
        with self._lock:
            self.send_s += seconds
            self.bytes_out += nbytes
            self.messages_out += 1

    def record_rtt(self, seconds: float) -> None:
        """Header only: hidden-out -> token-back ring round trip."""
        with self._lock:
            self._rtt_samples.append(seconds)

    # -- reading -----------------------------------------------------------

    def snapshot(self, include_samples: bool = False) -> dict:
        """``include_samples`` adds the raw per-step reservoirs (FIFO
        order): with one request in flight the header's rtt sample i and
        the tail's compute sample i belong to the same token step, so a
        consumer can estimate the per-hop network latency as the PAIRED
        residual ``(rtt_i - tail_compute_i)/2`` — aggregate percentiles
        can't (compute variance swamps the hop when the tail is slow)."""
        with self._lock:
            rtt = list(self._rtt_samples)
            comp = list(self._compute_samples)
            out = {
                "role": self.role,
                "uptime_s": round(time.time() - self.started_at, 3),
                "steps": self.steps,
                "recv_wait_s": round(self.recv_wait_s, 6),
                "compute_s": round(self.compute_s, 6),
                "send_s": round(self.send_s, 6),
                "bytes_in": self.bytes_in,
                "bytes_out": self.bytes_out,
                "messages_in": self.messages_in,
                "messages_out": self.messages_out,
            }
        if comp:
            xs = sorted(comp)    # one sort; _percentile re-sorts in O(n)
            out["compute_p50_ms"] = round(_percentile(xs, 50) * 1e3, 3)
            out["compute_p95_ms"] = round(_percentile(xs, 95) * 1e3, 3)
            out["compute_p99_ms"] = round(_percentile(xs, 99) * 1e3, 3)
        if rtt:
            xs = sorted(rtt)
            out["ring_rtt_p50_ms"] = round(_percentile(xs, 50) * 1e3, 3)
            out["ring_rtt_p95_ms"] = round(_percentile(xs, 95) * 1e3, 3)
            out["ring_rtt_p99_ms"] = round(_percentile(xs, 99) * 1e3, 3)
        if include_samples:
            out["compute_samples_ms"] = [round(s * 1e3, 3) for s in comp]
            out["rtt_samples_ms"] = [round(s * 1e3, 3) for s in rtt]
        return out


# span timing lives in telemetry.tracing.SpanClock (wall-clock start
# captured at open + perf_counter duration) — the old duration-only
# ``timer()`` helper was removed with the SpanClock migration so future
# instrumentation cannot reintroduce the wall/perf clock mixing.
