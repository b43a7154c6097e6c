"""Hand-rolled Prometheus metrics: registry, counter/gauge/histogram, text
exposition.

The reference system's only metrics surface was a stdout dump of
``commutimeArraySum``/``infertimeArraySum`` at run end
(``Communication.java:650-661``); our port grew an ad-hoc ``/stats`` JSON
blob.  This module is the standard surface both converge on: a small
registry (NO new dependency — the container has no prometheus_client)
rendering Prometheus text exposition format 0.0.4, scraped at
``GET /metrics`` on the header HTTP server and on every worker
(``MetricsHTTPServer``).

Conventions (enforced by ``tools/check_metrics_names.py``):

- names are ``dwt_<subsystem>_<name>_<unit>`` with counters additionally
  suffixed ``_total`` (Prometheus convention);
- every metric carries non-empty help text;
- histograms use FIXED buckets chosen at registration (cumulative,
  ``+Inf`` always present, ``_count``/``_sum`` consistent) so scrapes are
  O(buckets) regardless of traffic.
"""

from __future__ import annotations

import re
import threading
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# default latency buckets: 1 ms .. 60 s, roughly x4 steps — wide enough for
# sub-ms decode steps and multi-second prefills alike, without
# per-deployment tuning
LATENCY_BUCKETS_S = (0.001, 0.004, 0.016, 0.064, 0.25, 1.0, 4.0, 15.0, 60.0)


class MetricError(ValueError):
    """Bad metric name / labels / usage."""


def _fmt(v: float) -> str:
    """Prometheus sample value formatting: integers without the trailing
    .0, +Inf/NaN spelled the Prometheus way."""
    if v == float("inf"):
        return "+Inf"
    if v != v:  # NaN
        return "NaN"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(s: str) -> str:
    return (s.replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _render_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label(str(v))}"' for k, v in labels)
    return "{" + inner + "}"


class Metric:
    """Base: a named family with optional label dimensions.  Concrete
    classes own per-labelset children; ``samples()`` yields
    ``(suffix, label_pairs, value)`` rows for the renderer."""

    type: str = ""

    def __init__(self, name: str, help: str,
                 labels: Sequence[str] = ()) -> None:
        if not _NAME_RE.match(name):
            raise MetricError(f"bad metric name {name!r}")
        if not help or not help.strip():
            raise MetricError(f"metric {name!r} needs help text")
        for l in labels:
            if not _LABEL_RE.match(l):
                raise MetricError(f"bad label name {l!r} on {name!r}")
        self.name = name
        self.help = help.strip()
        self.label_names = tuple(labels)
        self._lock = threading.Lock()

    def _key(self, label_values: Dict[str, str]) -> Tuple[Tuple[str, str],
                                                          ...]:
        if set(label_values) != set(self.label_names):
            raise MetricError(
                f"{self.name}: labels {sorted(label_values)} != declared "
                f"{sorted(self.label_names)}")
        return tuple((k, str(label_values[k])) for k in self.label_names)

    def samples(self) -> Iterable[Tuple[str, Tuple[Tuple[str, str], ...],
                                        float]]:
        raise NotImplementedError


class Counter(Metric):
    """Monotone counter.  ``inc`` rejects negative deltas; ``set_cumulative``
    bridges an external cumulative value (e.g. a StageStats snapshot) and
    tolerates resets the way Prometheus counters do (value drops are kept,
    rate() handles them)."""

    type = "counter"

    def __init__(self, name, help, labels=()):
        super().__init__(name, help, labels)
        self._values: Dict[tuple, float] = {}

    def labels(self, **kv) -> "_CounterChild":
        return _CounterChild(self, self._key(kv))

    def inc(self, amount: float = 1.0, **kv) -> None:
        if amount < 0:
            raise MetricError(f"{self.name}: counter inc must be >= 0")
        key = self._key(kv)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def set_cumulative(self, value: float, **kv) -> None:
        key = self._key(kv)
        with self._lock:
            self._values[key] = float(value)

    def samples(self):
        with self._lock:
            items = sorted(self._values.items())
        if not self.label_names and not items:
            items = [((), 0.0)]      # unlabeled counters always render
        for key, v in items:
            yield "", key, v


class _CounterChild:
    __slots__ = ("_m", "_key")

    def __init__(self, m: Counter, key):
        self._m, self._key = m, key

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise MetricError(f"{self._m.name}: counter inc must be >= 0")
        with self._m._lock:
            self._m._values[self._key] = \
                self._m._values.get(self._key, 0.0) + amount


class Gauge(Metric):
    """Settable value; optionally backed by a callback sampled at render
    time (``set_function`` — e.g. live queue depth)."""

    type = "gauge"

    def __init__(self, name, help, labels=()):
        super().__init__(name, help, labels)
        self._values: Dict[tuple, float] = {}
        self._fn: Optional[Callable[[], float]] = None

    def set(self, value: float, **kv) -> None:
        key = self._key(kv)
        with self._lock:
            self._values[key] = float(value)

    def inc(self, amount: float = 1.0, **kv) -> None:
        key = self._key(kv)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def set_function(self, fn: Callable[[], float]) -> None:
        if self.label_names:
            raise MetricError(
                f"{self.name}: callback gauges cannot be labeled")
        self._fn = fn

    def samples(self):
        if self._fn is not None:
            try:
                yield "", (), float(self._fn())
            except Exception:
                yield "", (), float("nan")
            return
        with self._lock:
            items = sorted(self._values.items())
        if not self.label_names and not items:
            items = [((), 0.0)]      # unlabeled gauges always render
        for key, v in items:
            yield "", key, v


class Histogram(Metric):
    """Fixed-bucket histogram.  Buckets are upper bounds (le); the
    renderer emits cumulative counts, a ``+Inf`` bucket, ``_count`` and
    ``_sum`` — the shape PromQL's ``histogram_quantile`` expects."""

    type = "histogram"

    def __init__(self, name, help, labels=(),
                 buckets: Sequence[float] = LATENCY_BUCKETS_S):
        super().__init__(name, help, labels)
        bs = sorted(float(b) for b in buckets)
        if not bs:
            raise MetricError(f"{self.name}: needs at least one bucket")
        if len(set(bs)) != len(bs):
            raise MetricError(f"{self.name}: duplicate buckets")
        self.buckets = tuple(bs)
        # per-labelset: ([per-bucket counts] + [inf count], sum)
        self._data: Dict[tuple, list] = {}

    def observe(self, value: float, **kv) -> None:
        key = self._key(kv)
        v = float(value)
        with self._lock:
            st = self._data.get(key)
            if st is None:
                st = self._data[key] = [[0] * (len(self.buckets) + 1), 0.0]
            counts, _ = st
            for i, b in enumerate(self.buckets):
                if v <= b:
                    counts[i] += 1
                    break
            else:
                counts[-1] += 1
            st[1] += v

    def labels(self, **kv) -> "_HistChild":
        key = self._key(kv)          # validate eagerly
        return _HistChild(self, kv)

    def samples(self):
        with self._lock:
            items = sorted((k, ([*c], s)) for k, (c, s)
                           in self._data.items())
        if not self.label_names and not items:
            items = [((), ([0] * (len(self.buckets) + 1), 0.0))]
        for key, (counts, total) in items:
            cum = 0
            for b, c in zip(self.buckets, counts):
                cum += c
                yield "_bucket", key + (("le", _fmt(b)),), float(cum)
            cum += counts[-1]
            yield "_bucket", key + (("le", "+Inf"),), float(cum)
            yield "_count", key, float(cum)
            yield "_sum", key, total


class _HistChild:
    __slots__ = ("_m", "_kv")

    def __init__(self, m: Histogram, kv):
        self._m, self._kv = m, kv

    def observe(self, value: float) -> None:
        self._m.observe(value, **self._kv)


class Registry:
    """Metric families in registration order; ``render()`` is the text
    exposition payload for ``GET /metrics``."""

    def __init__(self):
        self._metrics: "Dict[str, Metric]" = {}
        self._lock = threading.Lock()

    def register(self, metric: Metric) -> Metric:
        with self._lock:
            if metric.name in self._metrics:
                raise MetricError(f"duplicate metric {metric.name!r}")
            self._metrics[metric.name] = metric
        return metric

    def unregister(self, name: str) -> None:
        with self._lock:
            self._metrics.pop(name, None)

    def collect(self) -> List[Metric]:
        with self._lock:
            return list(self._metrics.values())

    def render(self) -> str:
        lines: List[str] = []
        for m in self.collect():
            lines.append(f"# HELP {m.name} {_escape_help(m.help)}")
            lines.append(f"# TYPE {m.name} {m.type}")
            for suffix, label_pairs, value in m.samples():
                lines.append(f"{m.name}{suffix}"
                             f"{_render_labels(tuple(label_pairs))} "
                             f"{_fmt(value)}")
        return "\n".join(lines) + "\n"


# the process-default registry every subsystem registers into (see
# telemetry/catalog.py for the standard metric set)
REGISTRY = Registry()


def counter(name, help, labels=(), registry: Optional[Registry] = None):
    return (registry or REGISTRY).register(Counter(name, help, labels))


def gauge(name, help, labels=(), registry: Optional[Registry] = None):
    return (registry or REGISTRY).register(Gauge(name, help, labels))


def histogram(name, help, labels=(), buckets=LATENCY_BUCKETS_S,
              registry: Optional[Registry] = None):
    return (registry or REGISTRY).register(
        Histogram(name, help, labels, buckets))


class MetricsHTTPServer:
    """Minimal threaded ``GET /metrics`` endpoint for processes that have
    no other HTTP surface (pipeline stage workers — the header's main
    server exposes /metrics itself).  ``provider()`` returns the rendered
    text at scrape time.  ``debug_provider()`` (optional) returns a dict
    served as JSON at ``GET /debugz`` — live flight-recorder/anomaly
    state for operators poking a single worker."""

    def __init__(self, provider: Callable[[], str],
                 host: str = "127.0.0.1", port: int = 0,
                 debug_provider: Optional[Callable[[], dict]] = None):
        import json as _json
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):   # quiet
                pass

            def do_GET(self):
                ctype = "text/plain; version=0.0.4; charset=utf-8"
                # query strings are ignored, matching the header HTTP
                # server's routing (a cache-busting ?x=1 must not 404)
                path = self.path.split("?")[0]
                if (debug_provider is not None
                        and path in ("/debugz", "/debugz/")):
                    ctype = "application/json"
                    try:
                        body = _json.dumps(debug_provider(),
                                           default=str).encode("utf-8")
                        self.send_response(200)
                    except Exception as e:
                        body = _json.dumps({"error": str(e)}).encode()
                        self.send_response(500)
                elif path not in ("/metrics", "/metrics/"):
                    body = b"see /metrics\n"
                    self.send_response(404)
                else:
                    try:
                        body = provider().encode("utf-8")
                        self.send_response(200)
                    except Exception as e:      # scrape must never 500 the
                        body = f"# scrape error: {e}\n".encode()
                        self.send_response(500)  # worker loop
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self.httpd.server_address
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def shutdown(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=10)
