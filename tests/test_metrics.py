"""Prometheus /metrics endpoint: text exposition validity, counter
monotonicity across generate calls, histogram bucket sanity (ISSUE 1
satellite).  The registry/classes themselves are also unit-covered here
(the handlers are plumbing; the format rules live in telemetry/metrics).
"""

import json
import re
import urllib.request

import numpy as np
import pytest

import jax

from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.http_server import (
    InferenceHTTPServer)
from distributed_inference_demo_tpu.telemetry.metrics import (
    Counter, Gauge, Histogram, MetricError, Registry)

MODEL = "llama-test"
PROMPT = [[5, 17, 42, 7]]

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^}]*\})?\s+(?P<value>\S+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text):
    """Parse Prometheus text format line by line; assert structural
    validity (HELP/TYPE before samples, parseable sample lines).
    Returns ({(name, frozen_labels): value}, {family: type})."""
    samples, types, helped = {}, {}, set()
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            _, _, fam, typ = line.split(None, 3)
            assert typ in ("counter", "gauge", "histogram"), line
            assert fam in helped, f"TYPE before HELP: {line}"
            types[fam] = typ
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        labels = frozenset(_LABEL_RE.findall(m.group("labels") or ""))
        v = m.group("value")
        value = float("inf") if v == "+Inf" else float(v)
        key = (m.group("name"), labels)
        assert key not in samples, f"duplicate sample: {line!r}"
        samples[key] = value
        base = m.group("name")
        for suffix in ("_bucket", "_count", "_sum"):
            if base.endswith(suffix) and base[:-len(suffix)] in types:
                base = base[:-len(suffix)]
        assert base in types, f"sample without TYPE: {line!r}"
    return samples, types


def _get(url):
    with urllib.request.urlopen(url, timeout=60) as r:
        ctype = r.headers.get("Content-Type", "")
        return r.read().decode("utf-8"), ctype


def _post(url, body):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        # a streamed reply is JSONL: one object a line
        return [json.loads(l) for l in r.read().splitlines()]


@pytest.fixture(scope="module")
def served_engine():
    cfg = get_model_config(MODEL)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(cfg, params, max_seq=64,
                             sampling=SamplingParams(greedy=True))
    server = InferenceHTTPServer(engine, port=0, model_name=MODEL)
    server.start()
    yield f"http://{server.host}:{server.port}"
    server.shutdown()


def _histo(samples, name, labels=frozenset()):
    """(sorted bucket (le, cum) list, count, sum) for one histogram
    child."""
    buckets = []
    for (n, lab), v in samples.items():
        if n == name + "_bucket" and labels <= lab:
            le = dict(lab)["le"]
            buckets.append((float("inf") if le == "+Inf" else float(le),
                            v))
    count = samples[(name + "_count", labels)]
    total = samples[(name + "_sum", labels)]
    return sorted(buckets), count, total


@pytest.mark.quick
@pytest.mark.parametrize("stream", [False, True])
def test_metrics_scrape_counters_and_histogram(served_engine, stream):
    """The layer's series move on a streamed request as on one that is
    not: both are fed from the request's passage through the handler
    (``t_accept`` to the last write; the tokens written)."""
    url = served_engine
    body = {"prompt_ids": PROMPT, "max_new_tokens": 3, "stream": stream}
    assert len(_post(url + "/generate", body)) == (3 if stream else 1)
    text1, ctype = _get(url + "/metrics")
    assert ctype.startswith("text/plain")
    assert "version=0.0.4" in ctype
    s1, types1 = parse_exposition(text1)

    _post(url + "/generate", body)
    text2, _ = _get(url + "/metrics")
    s2, types2 = parse_exposition(text2)

    # counter monotonicity across the two generate calls
    req_key = ("dwt_http_requests_total",
               frozenset({("route", "/generate"), ("code", "200")}))
    assert req_key in s1 and s2[req_key] == s1[req_key] + 1
    tok_key = ("dwt_http_generated_tokens_total", frozenset())
    assert s2[tok_key] == s1[tok_key] + 3
    # EVERY counter sample is monotone between the scrapes
    for (name, labels), v in s1.items():
        fam = name[:-len("_bucket")] if name.endswith("_bucket") else name
        fam = fam[:-len("_count")] if fam.endswith("_count") else fam
        fam = fam[:-len("_sum")] if fam.endswith("_sum") else fam
        if types1.get(name) == "counter" and (name, labels) in s2:
            assert s2[(name, labels)] >= v, name

    # histogram sanity: cumulative buckets, +Inf present, _count/_sum
    # consistent with the observations.  Counts are DELTAS between the
    # scrapes — the registry is process-global and other tests in the
    # suite observe into it too.
    lab = frozenset({("route", "/generate")})
    _, count1, total1 = _histo(s1, "dwt_http_request_seconds", lab)
    buckets, count, total = _histo(s2, "dwt_http_request_seconds", lab)
    assert buckets, "no histogram buckets rendered"
    assert buckets[-1][0] == float("inf"), "+Inf bucket missing"
    cums = [c for _, c in buckets]
    assert cums == sorted(cums), "buckets must be cumulative"
    assert cums[-1] == count            # +Inf bucket == _count
    assert count == count1 + 1          # one generate between scrapes
    assert total >= total1 >= 0         # _sum is monotone
    # _sum stays consistent with the bucket layout's value range
    assert total - total1 <= 60.0 + 1e-9   # one obs <= top finite bucket
                                           # (requests here take < 60 s)

    # the standard series families render even before their subsystems
    # run: batching + monitor + stage families are present
    assert types2.get("dwt_batching_queue_depth_requests") == "gauge"
    mem_total = ("dwt_monitor_host_memory_bytes",
                 frozenset({("kind", "total")}))
    assert s2[mem_total] > 0


def test_metrics_endpoint_never_500s_on_statless_backend(served_engine):
    # plain engines have no .stats(); the scrape still renders
    text, _ = _get(served_engine + "/metrics")
    parse_exposition(text)


def test_worker_metrics_server():
    """The standalone worker /metrics endpoint (worker_main
    --metrics-port): a MetricsHTTPServer over render_worker exposes the
    stage series for the worker's StageStats."""
    from distributed_inference_demo_tpu.runtime.stats import StageStats
    from distributed_inference_demo_tpu.telemetry import MetricsHTTPServer
    from distributed_inference_demo_tpu.telemetry import catalog

    st = StageStats("worker")
    st.record_compute(0.01)
    st.record_recv(0.002, 1234)
    srv = MetricsHTTPServer(lambda: catalog.render_worker(st, "w9"),
                            port=0)
    srv.start()
    try:
        text, ctype = _get(f"http://{srv.host}:{srv.port}/metrics")
        assert ctype.startswith("text/plain")
        samples, _ = parse_exposition(text)
        lab = frozenset({("role", "worker"), ("device", "w9")})
        assert samples[("dwt_stage_steps_total", lab)] == 1
        assert samples[("dwt_stage_recv_bytes_total", lab)] == 1234
        # non-/metrics paths 404 without breaking the loop
        req = urllib.request.Request(
            f"http://{srv.host}:{srv.port}/other")
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 404
    finally:
        srv.shutdown()


def test_worker_metrics_server_debugz():
    """The worker-side GET /debugz (worker_main --metrics-port): JSON
    flight/postmortem state next to the text /metrics scrape."""
    from distributed_inference_demo_tpu.runtime.stats import StageStats
    from distributed_inference_demo_tpu.telemetry import (
        FlightRecorder, MetricsHTTPServer, set_flight_recorder)
    from distributed_inference_demo_tpu.telemetry import catalog

    fr = FlightRecorder(proc="w9", max_events=16)
    set_flight_recorder(fr)
    fr.record("hop_recv", rid=1, step=2)
    st = StageStats("worker")

    def debugz():
        return {"device_id": "w9",
                "flight": {"total": fr.total, "tail": fr.tail(8)}}

    srv = MetricsHTTPServer(lambda: catalog.render_worker(st, "w9"),
                            port=0, debug_provider=debugz)
    srv.start()
    try:
        text, ctype = _get(f"http://{srv.host}:{srv.port}/debugz")
        assert ctype.startswith("application/json")
        dz = json.loads(text)
        assert dz["device_id"] == "w9"
        assert dz["flight"]["tail"][0]["kind"] == "hop_recv"
        # the metrics path still serves text exposition alongside
        text, ctype = _get(f"http://{srv.host}:{srv.port}/metrics")
        assert ctype.startswith("text/plain")
        parse_exposition(text)
    finally:
        srv.shutdown()
        set_flight_recorder(None)


# -- registry / class unit tests -------------------------------------------

def test_counter_rejects_negative_and_duplicate_names():
    reg = Registry()
    c = Counter("dwt_http_x_requests_total", "x", ("route",))
    reg.register(c)
    with pytest.raises(MetricError):
        reg.register(Counter("dwt_http_x_requests_total", "again"))
    with pytest.raises(MetricError):
        c.inc(-1, route="a")
    with pytest.raises(MetricError):
        c.inc(1, wrong_label="a")
    c.inc(2, route="a")
    c.labels(route="a").inc()
    assert list(c.samples()) == [("", (("route", "a"),), 3.0)]


def test_gauge_callback_and_default_render():
    g = Gauge("dwt_batching_depth_requests", "live depth")
    assert list(g.samples()) == [("", (), 0.0)]    # renders before set
    g.set_function(lambda: 7)
    assert list(g.samples()) == [("", (), 7.0)]


def test_histogram_bucket_edges():
    h = Histogram("dwt_http_y_seconds", "y", buckets=(0.1, 1.0))
    h.observe(0.1)     # le == bound lands IN the bucket (le semantics)
    h.observe(0.5)
    h.observe(99.0)    # overflows to +Inf only
    rows = list(h.samples())
    by_suffix = {}
    for suffix, labels, v in rows:
        by_suffix.setdefault(suffix, []).append((labels, v))
    les = {dict(l)["le"]: v for l, v in by_suffix["_bucket"]}
    assert les == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}
    assert by_suffix["_count"] == [((), 3.0)]
    assert abs(by_suffix["_sum"][0][1] - 99.6) < 1e-9


def test_render_escapes_and_formats():
    reg = Registry()
    g = Gauge("dwt_stage_z_seconds", 'help with "quotes"\nand newline',
              ("role",))
    reg.register(g)
    g.set(1.5, role='we"ird\nrole')
    text = reg.render()
    assert '\\n' in text.splitlines()[0]           # escaped help
    assert 'role="we\\"ird\\nrole"' in text
    assert text.endswith("\n")
    parse_exposition(text)
