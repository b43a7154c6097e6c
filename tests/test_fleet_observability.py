"""Fleet observability plane (ISSUE 16): federation, stitch, SLO e2e.

Two layers:

- unit tests over the federation text surgery (``relabel_sample``,
  ``merge_exposition``) and the :class:`FleetScraper` debounce /
  bounded-staleness cache — injected clock + fetcher, no sockets;
- THE acceptance e2e: two tenants stream through gateway -> replica
  with one request live-migrated mid-decode; ``/metrics/fleet`` shows
  per-tenant goodput and burn-rate series with ``replica=`` labels
  from both replicas; ``/trace/fleet`` yields ONE Chrome trace whose
  gateway-proxy, engine, and migration spans share the request's trace
  id; the migrated request's ``/timeline`` record shows the migration
  pause with a TTFT/TPOT decomposition summing to e2e; greedy output
  stays bit-identical and both pools end leak-free.
"""

import json
import re
import subprocess
import sys
import threading
import time
from http.client import HTTPConnection
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from distributed_inference_demo_tpu.comm.transport import (
    LoopbackNetwork, LoopbackTransport)
from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from distributed_inference_demo_tpu.runtime.gateway import (
    GatewayHTTPServer, PrefixAwareRouter, ReplicaRegistry)
from distributed_inference_demo_tpu.runtime.gateway.federation import (
    FleetScraper, merge_exposition, relabel_sample)
from distributed_inference_demo_tpu.runtime.http_server import (
    InferenceHTTPServer)
from distributed_inference_demo_tpu.runtime.migration import MigrationWorker
from distributed_inference_demo_tpu.telemetry import catalog as _catalog
from distributed_inference_demo_tpu.telemetry.slo import (
    SloLedger, set_slo_ledger)

GREEDY = SamplingParams(greedy=True)
CFG = get_model_config("llama-test")
PROMPT = (np.arange(17) % 50 + 3).astype(np.int32)
MAX_NEW = 96


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# unit: exposition text surgery
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_relabel_sample_variants():
    assert (relabel_sample('dwt_x_total{tenant="a"} 3', "h:1")
            == 'dwt_x_total{replica="h:1",tenant="a"} 3')
    assert (relabel_sample("dwt_x_total 3 1700000000", "h:1")
            == 'dwt_x_total{replica="h:1"} 3 1700000000')
    assert (relabel_sample("dwt_x_total{} 3", "h:1")
            == 'dwt_x_total{replica="h:1"} 3')
    # the injected label goes FIRST: a label value containing "{" or
    # an escaped quote can't confuse the splice
    tricky = 'dwt_x_total{k="a{b\\"c"} 1'
    assert (relabel_sample(tricky, "h:1")
            == 'dwt_x_total{replica="h:1",k="a{b\\"c"} 1')
    # rid itself is escaped into a valid label value
    assert 'replica="q\\"r"' in relabel_sample("m 1", 'q"r')


@pytest.mark.quick
def test_merge_exposition_dedups_headers_and_groups_families():
    gw = ("# HELP dwt_f_total doc\n# TYPE dwt_f_total counter\n"
          'dwt_f_total{route="/x"} 1\n')
    rep = ("# HELP dwt_f_total doc\n# TYPE dwt_f_total counter\n"
           'dwt_f_total{route="/x"} 5\n'
           "# HELP dwt_g_seconds other\n# TYPE dwt_g_seconds histogram\n"
           'dwt_g_seconds_bucket{le="+Inf"} 2\n'
           "dwt_g_seconds_sum 0.1\ndwt_g_seconds_count 2\n")
    page = merge_exposition([(None, gw), ("r:1", rep)])
    # headers appear once, first-wins
    assert page.count("# HELP dwt_f_total") == 1
    assert page.count("# TYPE dwt_f_total") == 1
    # gateway's own samples stay bare; the replica's gain replica=
    assert 'dwt_f_total{route="/x"} 1' in page
    assert 'dwt_f_total{replica="r:1",route="/x"} 5' in page
    # histogram children follow their family header (contiguity): every
    # sample of a family sits between its header and the next one
    assert 'dwt_g_seconds_bucket{replica="r:1",le="+Inf"} 2' in page
    f_block = page.split("# HELP dwt_g_seconds")[0]
    assert "dwt_g_seconds" not in f_block.replace(
        "# HELP dwt_g_seconds", "")
    assert page.index("dwt_f_total{replica") < page.index(
        "# HELP dwt_g_seconds")


class _FakeRegistry:
    def __init__(self, rids):
        self.rids = list(rids)

    def replica_ids(self):
        return list(self.rids)

    def endpoint(self, rid):
        host, port = rid.rsplit(":", 1)
        return host, int(port)


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.mark.quick
def test_fleet_scraper_debounce_staleness_and_holes():
    clk = _Clock()
    calls = []
    fail = {"flag": False}

    def fetcher(host, port):
        calls.append((host, port))
        if fail["flag"]:
            raise RuntimeError("replica down")
        return ("# HELP dwt_u_total doc\n# TYPE dwt_u_total counter\n"
                "dwt_u_total 7\n")

    fs = FleetScraper(_FakeRegistry(["h:9"]), min_interval_s=1.0,
                      max_stale_s=30.0, clock=clk, fetcher=fetcher)
    own = "# HELP dwt_o_total d\n# TYPE dwt_o_total counter\ndwt_o_total 1\n"
    page = fs.scrape_fleet(own)
    assert 'dwt_u_total{replica="h:9"} 7' in page
    assert "dwt_o_total 1" in page            # gateway stays bare
    # debounce: a second scrape inside the window reuses the cache
    clk.t += 0.5
    fs.scrape_fleet(own)
    assert len(calls) == 1
    # fetch failures inside max_stale serve the last good text
    fail["flag"] = True
    clk.t += 2.0
    page = fs.scrape_fleet(own)
    assert len(calls) == 2                    # attempted, failed
    assert 'dwt_u_total{replica="h:9"} 7' in page
    assert ('dwt_gateway_fleet_failed_scrapes_total{replica="h:9"} 1'
            in _catalog.REGISTRY.render())
    # beyond max_stale the section degrades to a visible hole
    clk.t += 60.0
    page = fs.scrape_fleet(own)
    assert "dwt_u_total" not in page
    assert "# replica h:9: no scrape within 30s" in page
    # recovery repopulates
    fail["flag"] = False
    clk.t += 2.0
    assert 'dwt_u_total{replica="h:9"} 7' in fs.scrape_fleet(own)
    assert fs.debug_state()["h:9"]["cached"] is True


# ---------------------------------------------------------------------------
# the acceptance e2e
# ---------------------------------------------------------------------------


def _get(host, port, path, timeout=60):
    conn = HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _post_stream(host, port, body, headers=None, timeout=300):
    conn = HTTPConnection(host, port, timeout=timeout)
    try:
        hs = {"Content-Type": "application/json"}
        hs.update(headers or {})
        conn.request("POST", "/generate", body=json.dumps(body),
                     headers=hs)
        resp = conn.getresponse()
        rhead = dict(resp.getheaders())
        if resp.status != 200:
            return resp.status, rhead, [json.loads(resp.read())]
        lines = []
        while True:
            ln = resp.readline()
            if not ln:
                break
            ln = ln.strip()
            if ln:
                lines.append(json.loads(ln))
        return resp.status, rhead, lines
    finally:
        conn.close()


def _drain(gw, rid, flag=True):
    conn = HTTPConnection(gw.host, gw.port, timeout=30)
    try:
        conn.request("POST", "/drain", body=json.dumps(
            {"replica": rid, "draining": flag}))
        assert conn.getresponse().status == 200
    finally:
        conn.close()


def _idle_no_leaks(*engines):
    deadline = time.monotonic() + 5.0
    while True:
        snaps = [e.kv_cache.snapshot() for e in engines]
        if all(s["blocks_used"] == s["tree_blocks"] for s in snaps):
            return
        if time.monotonic() > deadline:
            raise AssertionError("page leak: " + ", ".join(
                f"{s['blocks_used']}/{s['tree_blocks']}" for s in snaps))
        time.sleep(0.05)


# tier-1 budget: the scraper/relabel/merge quick tests pin the fleet
# plane; the two-tenant live-migration soak rides the slow lane
@pytest.mark.slow
def test_two_tenant_fleet_with_live_migration_end_to_end(params):
    """ISSUE-16 acceptance: see module docstring."""
    set_slo_ledger(SloLedger(ttft_slo_ms=0, tpot_slo_ms=0, target=0.99))
    ref_eng = ContinuousBatchingEngine(
        CFG, params, max_seq=160, max_batch=2, sampling=GREEDY,
        kv_cache_blocks=32, kv_block_tokens=8)
    try:
        reference = [int(t) for t in ref_eng.submit(PROMPT,
                                                    MAX_NEW).wait(120)]
    finally:
        ref_eng.close()

    engines = [ContinuousBatchingEngine(
        CFG, params, max_seq=160, max_batch=2, sampling=GREEDY,
        kv_cache_blocks=32, kv_block_tokens=8) for _ in range(2)]
    net = LoopbackNetwork()
    workers = [MigrationWorker(eng, LoopbackTransport(name, net),
                               ack_timeout=10.0)
               for eng, name in zip(engines, ("r1", "r2"))]
    threads = [threading.Thread(target=w.serve_forever, daemon=True)
               for w in workers]
    for t in threads:
        t.start()
    servers = []
    for eng in engines:
        srv = InferenceHTTPServer(eng, port=0)
        srv.start()
        servers.append(srv)
    rids = [f"{s.host}:{s.port}" for s in servers]
    registry = ReplicaRegistry([(s.host, s.port) for s in servers],
                               sustain=3, probe_interval_s=0.2)
    router = PrefixAwareRouter(registry, min_prefix_tokens=8,
                               block_tokens=8)
    gw = GatewayHTTPServer(registry, router, port=0,
                           fleet_scrape_interval_s=0.0)
    gw.start()
    try:
        # ---- tenant-a: long stream pinned to replica 1 by draining 2
        _drain(gw, rids[1], True)
        result_a = {}

        def run_a():
            result_a["resp"] = _post_stream(
                gw.host, gw.port,
                {"prompt_ids": [[int(t) for t in PROMPT]],
                 "max_new_tokens": MAX_NEW, "stream": True,
                 "tenant": "tenant-a"})

        ta = threading.Thread(target=run_a)
        ta.start()
        deadline = time.monotonic() + 60.0
        migratable = []
        while not migratable and time.monotonic() < deadline:
            migratable = workers[0].pick_migratable(4)
            time.sleep(0.002)
        assert migratable, "tenant-a request never became migratable"

        # ---- flip the drain: tenant-b lands on replica 2, and the
        # live request migrates there mid-decode
        _drain(gw, rids[1], False)
        _drain(gw, rids[0], True)
        assert workers[0].migrate_out(migratable[0], "r2") is True

        st, headers, _ = _post_stream(
            gw.host, gw.port,
            {"prompt_ids": [[int(t) + 1 for t in PROMPT]],
             "max_new_tokens": 8, "stream": True},
            headers={"X-DWT-Tenant": "tenant-b"})
        assert st == 200
        assert headers["X-DWT-Replica"] == rids[1]

        ta.join(timeout=180)
        assert not ta.is_alive()
        st, _, lines = result_a["resp"]
        assert st == 200
        assert "error" not in lines[-1]
        # greedy bit-identity across the gateway hop AND the migration
        assert [d["tokens"][0] for d in lines] == reference
        _idle_no_leaks(*engines)

        # ---- /metrics/fleet: per-tenant series with replica= labels
        # from BOTH replicas, goodput + burn-rate present
        st, body = _get(gw.host, gw.port, "/metrics/fleet")
        assert st == 200
        page = body.decode()
        for rid in rids:
            assert re.search(
                r'dwt_slo_tokens_total\{replica="%s",tenant="tenant-a"\}'
                % re.escape(rid), page), rid
            assert f'dwt_gateway_fleet_scrapes_total{{replica="{rid}"}}' \
                in page
        assert re.search(
            r'dwt_slo_good_tokens_total\{replica=[^}]*'
            r'tenant="tenant-a"\} 96', page)
        assert re.search(
            r'dwt_slo_burn_rate_ratio\{replica=[^}]*tenant="tenant-a",'
            r'window="5m"\}', page)
        assert re.search(
            r'dwt_slo_migrated_requests_total\{replica=[^}]*'
            r'tenant="tenant-a"\} 1', page)
        assert 'tenant="tenant-b"' in page
        # headers dedup across gateway + 2 replica sections
        assert page.count("# HELP dwt_slo_tokens_total") == 1

        # ---- /trace/fleet: ONE Chrome trace; the migrated request's
        # gateway-proxy, engine, and migration spans share a trace id
        st, body = _get(gw.host, gw.port, "/trace/fleet")
        assert st == 200
        trace = json.loads(body)
        events = trace["traceEvents"]
        by_name = {}
        for ev in events:
            if ev.get("ph") == "X":
                by_name.setdefault(ev["name"], set()).add(
                    ev["args"]["trace_id"])
        gw_tids = by_name.get("gateway.proxy", set())
        eng_tids = (by_name.get("engine.prefill", set())
                    | by_name.get("engine.decode", set()))
        mig_tids = (by_name.get("migration_export", set())
                    & by_name.get("migration_handoff", set())
                    & by_name.get("migration_adopt", set()))
        stitched = gw_tids & eng_tids & mig_tids
        assert len(stitched) == 1, (gw_tids, eng_tids, mig_tids)
        # distinct process lanes: gateway + both engines + migration
        procs = {ev["args"]["name"] for ev in events
                 if ev.get("ph") == "M"
                 and ev.get("name") == "process_name"}
        assert "gateway" in procs
        assert len([p for p in procs if p.startswith("engine:")]) == 2
        assert any(p.startswith("migration:") for p in procs)

        # ---- /timeline on the SOURCE replica: the migrated record
        # decomposes, pause visible, sums to e2e
        st, body = _get(servers[0].host, servers[0].port,
                        "/timeline?n=32")
        assert st == 200
        tl = json.loads(body)
        recs = [r for r in tl["recent"]
                if r["tenant"] == "tenant-a" and r["migrated"]]
        assert len(recs) == 1
        r = recs[0]
        assert r["tokens"] == MAX_NEW
        assert r["migration_pause_s"] > 0.0
        assert r["trace_id"] in stitched
        lhs = (r["ttft_s"] + r["per_token_s"] * (r["tokens"] - 1)
               + r["migration_pause_s"])
        assert lhs == pytest.approx(r["e2e_s"], abs=1e-9)
        assert tl["tenants"]["tenant-a"]["migrated"] == 1

        # ---- gateway /debugz carries the probed fleet SLO summary
        deadline = time.monotonic() + 10.0
        fleet_slo = {}
        while time.monotonic() < deadline:
            st, body = _get(gw.host, gw.port, "/debugz")
            assert st == 200
            fleet_slo = json.loads(body)["fleet_slo"]
            if any("tenant-a" in v.get("tenants", {})
                   for v in fleet_slo.values()):
                break
            time.sleep(0.2)
        assert any("tenant-a" in v.get("tenants", {})
                   for v in fleet_slo.values())

        # ---- tools/fleet_top.py renders the same page (--once mode)
        proc = subprocess.run(
            [sys.executable, "tools/fleet_top.py",
             "--gateway", f"{gw.host}:{gw.port}", "--once"],
            cwd=str(Path(__file__).resolve().parent.parent),
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert "tenant-a" in proc.stdout
        assert "tenant-b" in proc.stdout
        assert rids[0] in proc.stdout
    finally:
        gw.shutdown()
        for w in workers:
            w.stop()
        for t in threads:
            t.join(timeout=2)
        for srv, eng in zip(servers, engines):
            srv.shutdown()
            eng.close()
        set_slo_ledger(None)


@pytest.mark.quick
@pytest.mark.parametrize("path", ["/trace/fleet", "/trace"])
def test_one_trace_id_holds_the_requests_whole_path(params, path):
    """One proxied streamed request, one id: the gateway's ``route`` and
    ``proxy``, the replica handler's ``http.ingress``, the engine's
    ``prefill`` and ``decode`` and, beside the decode, the handler's
    ``http.egress``.  ``/trace/fleet`` stitches the six into one trace;
    the gateway's and the replica's own ``/trace`` hold them between
    them.  The seconds the gateway held the request lie in the
    handler's span and in the replica's request-path record."""
    eng = ContinuousBatchingEngine(
        CFG, params, max_seq=96, max_batch=2, sampling=GREEDY,
        kv_cache_blocks=0, kv_block_tokens=8, decode_block=4)
    srv = InferenceHTTPServer(eng, port=0)
    srv.start()
    registry = ReplicaRegistry([(srv.host, srv.port)], sustain=3,
                               probe_interval_s=0.2)
    router = PrefixAwareRouter(registry, min_prefix_tokens=8,
                               block_tokens=8)
    gw = GatewayHTTPServer(registry, router, port=0)
    gw.start()
    try:
        st, _, lines = _post_stream(
            gw.host, gw.port, {"prompt_ids": [[int(t) for t in PROMPT]],
                               "max_new_tokens": 9, "stream": True})
        assert st == 200 and len(lines) == 9
        # the replica's handler and the gateway's record their spans at
        # the request's end, which lies behind the last byte the client
        # reads; an export takes what it gives, so read until both are in
        events, deadline = [], time.monotonic() + 30
        while not {"http.egress", "gateway.proxy"} <= {
                ev["name"] for ev in events}:
            assert time.monotonic() < deadline
            if path == "/trace/fleet":
                bodies = [_get(gw.host, gw.port, path)]
            else:
                bodies = [_get(gw.host, gw.port, path),
                          _get(srv.host, srv.port, path)]
            assert all(st == 200 for st, _ in bodies)
            events += [ev for _, body in bodies
                       for ev in json.loads(body)["traceEvents"]
                       if ev.get("ph") == "X"]
        [tid] = {ev["args"]["trace_id"] for ev in events
                 if ev["name"] == "gateway.route"}
        mine = {ev["name"]: ev for ev in events
                if ev["args"]["trace_id"] == tid}
        assert set(mine) == {"gateway.route", "gateway.proxy",
                             "http.ingress", "engine.prefill",
                             "engine.decode", "http.egress"}
        ing, pre = mine["http.ingress"], mine["engine.prefill"]
        # the handler's span ends where the wait in the queue begins ...
        assert abs(ing["ts"] + ing["dur"]
                   + 1e3 * pre["args"]["queue_wait_ms"] - pre["ts"]) <= 3
        # ... and lies inside the gateway's hop
        proxy = mine["gateway.proxy"]
        assert proxy["ts"] <= ing["ts"] + 2000
        assert ing["ts"] + ing["dur"] <= proxy["ts"] + proxy["dur"] + 2000
        eg = mine["http.egress"]["args"]
        # a line a token, a write a chunk of whole hand-offs (PR 59)
        assert eg["lines"] == 9 and eg["handoffs"] >= 2
        assert 1 <= eg["writes"] <= eg["handoffs"]
        # the gateway's seconds reached the replica: in the span and in
        # its record, whose row begins that much before the handler's
        rp = srv.request_path.snapshot()
        assert rp["ingress_count"] == 1 and rp["gateway_s"] > 0
        assert ing["args"]["gateway_ms"] == pytest.approx(
            1e3 * rp["gateway_s"], abs=1e-2)
        t_gateway, t_accept = rp["recent"][0][:2]
        assert t_accept - t_gateway == pytest.approx(rp["gateway_s"],
                                                     abs=2e-5)
    finally:
        gw.shutdown()
        srv.shutdown()
        eng.close()
