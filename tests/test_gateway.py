"""Gateway: prefix-aware routing, replica health debounce, proxy retry.

Three layers, cheapest first:

- pure unit tests over the router/registry decision logic (injected
  clock + prober, no sockets);
- HTTP-level tests against STUB replicas (a few dozen lines of
  ThreadingHTTPServer speaking just enough of the serving surface) —
  retry-before-first-token, 503 propagation, mid-stream socket death,
  /metrics + /debugz smoke;
- loopback soak over THREE real continuous-batching replicas, plus the
  mid-stream replica-kill chaos test reusing comm/faults crash rules —
  the greedy-oracle bit-identity contract survives the gateway hop.
"""

import json
import socket
import sys
import threading
from http.client import HTTPConnection
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from distributed_inference_demo_tpu.comm.faults import (FaultPlan,
                                                        FaultRule,
                                                        InjectedCrash)
from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime.batching import (
    ContinuousBatchingEngine)
from distributed_inference_demo_tpu.runtime.gateway import (
    GatewayHTTPServer, PrefixAwareRouter, ReplicaRegistry)
from distributed_inference_demo_tpu.runtime.http_server import (
    InferenceHTTPServer)
from distributed_inference_demo_tpu.runtime.overload import GatewayOverloaded
from distributed_inference_demo_tpu.telemetry.tracing import (
    GATEWAY_HELD_HEADER)

CFG = get_model_config("llama-test")
GREEDY = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def params():
    return init_full_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# unit: router + registry decision logic (no sockets)
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _registry(n=3, **kw):
    kw.setdefault("prober", lambda h, p: {"queue_depth": 0})
    return ReplicaRegistry([("10.0.0.1", 7000 + i) for i in range(n)],
                           **kw)


@pytest.mark.quick
def test_prefix_route_follows_history_and_falls_back_to_hash():
    router = PrefixAwareRouter(_registry(), min_prefix_tokens=8,
                               block_tokens=8)
    toks = list(range(2, 34))
    d0 = router.route(toks)
    assert d0.policy == "hash" and d0.match_tokens == 0
    # two alternates ride along for retry, in rendezvous order
    assert len(d0.candidates) == 2 and d0.rid not in d0.candidates
    router.record(d0.rid, toks)
    d1 = router.route(toks)
    assert d1.policy == "prefix" and d1.rid == d0.rid
    assert d1.match_tokens == 32
    # a prompt sharing only one block still follows (8 >= min_prefix)
    d2 = router.route(toks[:8] + [999] * 24)
    assert d2.policy == "prefix" and d2.rid == d0.rid
    assert d2.match_tokens == 8
    # an unrelated prompt hashes
    assert router.route([500 + i for i in range(32)]).policy == "hash"


@pytest.mark.quick
def test_short_match_stays_on_hash_fallback():
    router = PrefixAwareRouter(_registry(), min_prefix_tokens=16,
                               block_tokens=8)
    toks = list(range(2, 34))
    d0 = router.route(toks)
    router.record(d0.rid, toks)
    # only one 8-token block matches: below min_prefix_tokens=16
    d = router.route(toks[:8] + [999] * 24)
    assert d.policy == "hash" and d.match_tokens == 0


@pytest.mark.quick
def test_rendezvous_hash_is_deterministic_and_stable_under_eviction():
    reg = _registry(3, sustain=1)
    router = PrefixAwareRouter(reg, min_prefix_tokens=64, block_tokens=8)
    toks = list(range(2, 34))
    d1, d2 = router.route(toks), router.route(toks)
    assert d1.rid == d2.rid and d1.policy == d2.policy == "hash"
    # rendezvous property: evicting a NON-chosen replica moves nothing
    reg.record_failure(d1.candidates[-1])
    assert not reg.is_up(d1.candidates[-1])
    d3 = router.route(toks)
    assert d3.rid == d1.rid


@pytest.mark.quick
def test_bounded_load_skips_the_hot_hashed_pick():
    router = PrefixAwareRouter(_registry(), min_prefix_tokens=64,
                               block_tokens=8, load_factor=2.0)
    toks = list(range(2, 34))
    d = router.route(toks)
    for _ in range(12):           # load 12 > 2.0 * (1 + mean 4) = 10
        router.acquire(d.rid)
    d2 = router.route(toks)
    assert d2.rid != d.rid
    assert d2.rid == d.candidates[0]   # next in rendezvous order
    for _ in range(12):
        router.release(d.rid)
    assert router.route(toks).rid == d.rid


@pytest.mark.quick
def test_bounded_load_weighs_prefill_backlog_decision_table():
    """ISSUE-15 satellite: the bounded-load walk counts a replica's
    reported prefill backlog (``pending_prefill_tokens`` scaled by
    ``prefill_token_weight``) as queued work — a deep prompt backlog at
    ZERO queue depth sheds hashed traffic exactly like a deep queue,
    weight=0 restores the depth-only behavior, and a uniform backlog
    raises the mean with the load so it causes no churn."""
    toks = list(range(2, 34))

    def scenario(weight, depths, backlogs):
        reg = _registry()
        router = PrefixAwareRouter(reg, min_prefix_tokens=64,
                                   block_tokens=8, load_factor=1.0,
                                   prefill_token_weight=weight)
        d0 = router.route(toks)
        order = [d0.rid] + d0.candidates     # rendezvous order for toks
        for rid, dep, back in zip(order, depths, backlogs):
            reg.record_success(rid, {"queue_depth": dep,
                                     "pending_prefill_tokens": back})
        return order, router.route(toks).rid, router

    # nothing reported: the rendezvous-first replica serves
    order, got, _ = scenario(256, (0, 0, 0), (0, 0, 0))
    assert got == order[0]

    # deep backlog at zero depth sheds the pick: 4096/256 = 16
    # request-equivalents > bound 1.0 * (1 + 16/3)
    order, got, router = scenario(256, (0, 0, 0), (4096, 0, 0))
    assert got == order[1]
    assert router._load(order[0]) == 16.0

    # the same backlog with weight=0 is invisible (depth-only load)
    order, got, _ = scenario(0, (0, 0, 0), (4096, 0, 0))
    assert got == order[0]

    # uniform backlog raises the mean with the load: no churn
    order, got, _ = scenario(256, (0, 0, 0), (4096, 4096, 4096))
    assert got == order[0]

    # depth and backlog ADD: 2 + 1024/256 = 6 > bound 1.0 * (1 + 8/3);
    # the walk settles on the next replica (load 1)
    order, got, router = scenario(256, (2, 1, 1), (1024, 0, 0))
    assert got == order[1]

    # both knobs and the per-replica backlog surface on /debugz
    tab = router.routing_table()
    assert tab["prefill_token_weight"] == 256
    assert tab["replicas"][order[0]]["pending_prefill_tokens"] == 1024


@pytest.mark.quick
def test_bounded_load_weighs_spec_backlog_decision_table():
    """ISSUE-19 satellite: the bounded-load walk folds a replica's
    reported speculative backlog (``spec_backlog_tokens``, the active
    rows' Σ (K_row+1)·decode_block per-iteration spend, scaled by
    ``spec_token_weight``) into the same load it weighs prefill backlog
    with — a replica mid-speculation sheds hashed traffic, weight=0
    ignores it, uniform spec load causes no churn, and spec + prefill
    backlogs ADD."""
    toks = list(range(2, 34))

    def scenario(weight, depths, specs, prefills=(0, 0, 0)):
        reg = _registry()
        router = PrefixAwareRouter(reg, min_prefix_tokens=64,
                                   block_tokens=8, load_factor=1.0,
                                   prefill_token_weight=256,
                                   spec_token_weight=weight)
        d0 = router.route(toks)
        order = [d0.rid] + d0.candidates     # rendezvous order for toks
        for rid, dep, sp, pf in zip(order, depths, specs, prefills):
            reg.record_success(rid, {"queue_depth": dep,
                                     "spec_backlog_tokens": sp,
                                     "pending_prefill_tokens": pf})
        return order, router.route(toks).rid, router

    # nothing reported: rendezvous-first serves
    order, got, _ = scenario(256, (0, 0, 0), (0, 0, 0))
    assert got == order[0]

    # deep spec backlog at zero depth sheds the pick: 4096/256 = 16
    # request-equivalents > bound 1.0 * (1 + 16/3)
    order, got, router = scenario(256, (0, 0, 0), (4096, 0, 0))
    assert got == order[1]
    assert router._load(order[0]) == 16.0

    # the same backlog with weight=0 is invisible
    order, got, _ = scenario(0, (0, 0, 0), (4096, 0, 0))
    assert got == order[0]

    # uniform spec backlog raises the mean with the load: no churn
    order, got, _ = scenario(256, (0, 0, 0), (4096, 4096, 4096))
    assert got == order[0]

    # spec and prefill backlogs ADD: 512/256 + 1024/256 = 6 request-
    # equivalents > bound 1.0 * (1 + 2); the walk moves on
    order, got, router = scenario(256, (0, 0, 0), (512, 0, 0),
                                  (1024, 0, 0))
    assert got == order[1]
    assert router._load(order[0]) == 6.0

    # knob + per-replica gauge surface on /debugz
    tab = router.routing_table()
    assert tab["spec_token_weight"] == 256
    assert tab["replicas"][order[0]]["spec_backlog_tokens"] == 512


@pytest.mark.quick
def test_prefix_tie_breaks_toward_the_lighter_replica():
    reg = _registry()
    router = PrefixAwareRouter(reg, min_prefix_tokens=8, block_tokens=8)
    toks = list(range(2, 34))
    rids = reg.replica_ids()
    router.record(rids[0], toks)
    router.record(rids[1], toks)
    router.acquire(rids[0])
    d = router.route(toks)
    assert d.policy == "prefix" and d.rid == rids[1]


@pytest.mark.quick
def test_host_tier_second_chance_decision_table():
    """The §21 tier-aware route, as a decision table:

    1. device-tier miss everywhere + no tier digests -> hash fallback;
    2. device-tier miss + replica B's REPORTED host tier holds the
       prefix -> route to B with policy host_tier (NOT the rendezvous
       pick);
    3. device-tier history, once learned, wins over the tier hint;
    4. a match below min_prefix_tokens never second-chances;
    5. an empty digest report (tier drained/closed) withdraws B.
    """
    from distributed_inference_demo_tpu.runtime.kvcache.tiered import (
        chain_digests)
    reg = _registry(3)
    router = PrefixAwareRouter(reg, min_prefix_tokens=16, block_tokens=8)
    rids = reg.replica_ids()
    b = rids[1]
    toks = list(range(200, 232))                 # 4 blocks of 8
    keys = [tuple(toks[i * 8:(i + 1) * 8]) for i in range(4)]
    digests = [d.hex()[:16] for d in chain_digests(keys)]

    # row 1: nothing anywhere -> hash
    assert router.route(toks).policy == "hash"

    # row 2: B reports the prefix demoted (the /stats fragment the
    # registry prober carries) -> second chance routes to B
    router.reconcile(b, {"kvcache": {
        "tier": {"block_tokens": 8, "digest": digests}}})
    d = router.route(toks)
    assert d.policy == "host_tier"
    assert d.rid == b
    assert d.match_tokens == 32
    assert router.routing_table()["replicas"][b]["tier_digest_entries"] == 4

    # row 3: once replica A holds it in its DEVICE tree (gateway
    # history), the prefix policy outranks the tier hint
    a = rids[0]
    router.record(a, toks)
    d = router.route(toks)
    assert d.policy == "prefix" and d.rid == a

    # row 4: a one-block tier match (8 < min_prefix_tokens 16) is not
    # good enough — hash, not host_tier
    short = list(range(500, 516))
    short_digest = [chain_digests([tuple(short[:8])])[0].hex()[:16]]
    router.reconcile(b, {"kvcache": {
        "tier": {"block_tokens": 8, "digest": short_digest}}})
    assert router.route(short).policy == "hash"

    # row 5: an empty report withdraws the replica from second chances
    router.reconcile(b, {"kvcache": {
        "tier": {"block_tokens": 8, "digest": []}}})
    other = list(range(600, 632))
    router.reconcile(b, {"kvcache": {"tier": {"block_tokens": 8,
                                              "digest": []}}})
    assert router.route(other).policy == "hash"
    assert router.routing_table()["replicas"][b]["tier_digest_entries"] == 0


@pytest.mark.quick
def test_host_tier_flush_on_readmission_drops_digests():
    reg = _registry(2)
    router = PrefixAwareRouter(reg, min_prefix_tokens=8, block_tokens=8)
    from distributed_inference_demo_tpu.runtime.kvcache.tiered import (
        chain_digests)
    rid = reg.replica_ids()[0]
    toks = list(range(2, 18))
    dgs = [d.hex()[:16] for d in chain_digests(
        [tuple(toks[:8]), tuple(toks[8:])])]
    router.reconcile(rid, {"kvcache": {
        "tier": {"block_tokens": 8, "digest": dgs}}})
    assert router.tier_match_tokens(rid, toks) == 16
    # readmission flush: the replica restarted — its host ring is gone
    router.flush_replica(rid)
    assert router.tier_match_tokens(rid, toks) == 0


@pytest.mark.quick
def test_lru_trim_keeps_the_most_specific_prefix_keys():
    router = PrefixAwareRouter(_registry(), min_prefix_tokens=4,
                               block_tokens=4, max_index_entries=2)
    rid = router.registry.replica_ids()[0]
    toks = list(range(2, 18))     # 16 tokens -> 4 block keys, cap 2
    router.record(rid, toks)
    assert router.match_tokens(rid, toks) == 16
    # the short keys were the ones trimmed: an 8-token prefix misses
    assert router.match_tokens(rid, toks[:8]) == 0


@pytest.mark.quick
def test_eviction_readmission_debounce_with_injected_clock():
    clk = _Clock()
    reg = _registry(2, sustain=3, readmit_cooldown_s=5.0, clock=clk)
    router = PrefixAwareRouter(reg, min_prefix_tokens=8, block_tokens=8)
    rid = reg.replica_ids()[0]
    toks = list(range(2, 18))
    router.record(rid, toks)
    # two strikes: a blip, not an outage
    reg.record_failure(rid)
    reg.record_failure(rid)
    assert reg.is_up(rid)
    # a success wipes the streak entirely
    reg.record_success(rid)
    reg.record_failure(rid)
    reg.record_failure(rid)
    assert reg.is_up(rid)
    # the sustained third strike evicts
    reg.record_failure(rid)
    assert not reg.is_up(rid)
    assert rid not in reg.up_replicas()
    # a success INSIDE the cooldown clears the streak but does not
    # readmit — a flapping process must prove a quiet period
    clk.t += 2.0
    reg.record_success(rid, {"queue_depth": 0})
    assert not reg.is_up(rid)
    # past the cooldown a success readmits, and the router's history
    # for the replica is flushed (its cache state is unknown)
    clk.t += 4.0
    reg.record_success(rid, {"queue_depth": 0})
    assert reg.is_up(rid)
    assert router.match_tokens(rid, toks) == 0


@pytest.mark.quick
def test_probe_and_proxy_failures_share_one_streak():
    boom = RuntimeError("connection refused")

    def prober(host, port):
        raise boom

    reg = _registry(2, sustain=3, prober=prober)
    rid = reg.replica_ids()[0]
    reg.probe_all()                  # one strike per replica
    reg.record_failure(rid, reason="proxy: reset")   # strike 2
    assert reg.is_up(rid)
    reg.probe_all()                  # strike 3 evicts rid (and peer hits 2)
    assert not reg.is_up(rid)
    assert reg.is_up(reg.replica_ids()[1])


@pytest.mark.quick
def test_reconcile_flushes_history_when_replica_tree_resets():
    reg = _registry()
    router = PrefixAwareRouter(reg, min_prefix_tokens=8, block_tokens=8)
    rid = reg.replica_ids()[0]
    toks = list(range(2, 18))
    router.reconcile(rid, {"kvcache": {"nodes": 3}})
    router.record(rid, toks)
    assert router.match_tokens(rid, toks) == 16
    # same occupancy: nothing happens
    router.reconcile(rid, {"kvcache": {"nodes": 3}})
    assert router.match_tokens(rid, toks) == 16
    # the replica's tree emptied (restart / eviction storm): flush
    router.reconcile(rid, {"kvcache": {"nodes": 0}})
    assert router.match_tokens(rid, toks) == 0


@pytest.mark.quick
def test_route_raises_gateway_overloaded_when_all_replicas_down():
    reg = _registry(2, sustain=1)
    router = PrefixAwareRouter(reg, min_prefix_tokens=8, block_tokens=8)
    for rid in reg.replica_ids():
        reg.record_failure(rid)
    with pytest.raises(GatewayOverloaded):
        router.route(list(range(2, 18)))


@pytest.mark.quick
def test_draining_replica_stops_routing_without_a_strike():
    """The §18 drain satellite: a draining replica leaves
    routable_replicas (no NEW request routes to it) while staying UP —
    no eviction strike, health debounce untouched — and undraining
    restores it."""
    reg = _registry()
    router = PrefixAwareRouter(reg, min_prefix_tokens=8, block_tokens=8)
    victim = reg.replica_ids()[0]
    reg.set_draining(victim)
    assert reg.is_draining(victim)
    assert reg.is_up(victim)                 # health is orthogonal
    assert reg.get(victim).fail_streak == 0  # drain is NOT a strike
    assert victim in reg.up_replicas()
    assert victim not in reg.routable_replicas()
    # the router never picks it, prefix history or not
    router.record(victim, list(range(2, 34)))
    for salt in range(12):
        d = router.route(list(range(2, 34)) + [salt])
        assert d.rid != victim and victim not in d.candidates
    # surfaced on the debug planes
    assert reg.debug_state()["replicas"][victim]["draining"] is True
    assert router.routing_table()["replicas"][victim]["draining"] is True
    # idempotent set + undrain restores routing
    reg.set_draining(victim)
    reg.set_draining(victim, False)
    assert victim in reg.routable_replicas()
    assert not reg.is_draining(victim)


@pytest.mark.quick
def test_every_replica_draining_sheds_like_all_down():
    reg = _registry(2)
    router = PrefixAwareRouter(reg, min_prefix_tokens=8, block_tokens=8)
    for rid in reg.replica_ids():
        reg.set_draining(rid)
    with pytest.raises(GatewayOverloaded, match="draining"):
        router.route(list(range(2, 18)))


# ---------------------------------------------------------------------------
# HTTP-level: stub replicas (no engine, no jax compute)
# ---------------------------------------------------------------------------

class _StubReplica:
    """A replica double speaking just enough of the serving surface:
    ``GET /stats`` for the prober and a chunked-JSONL ``POST
    /generate``.  ``shed`` makes it answer 503/429 + Retry-After;
    ``sever_after`` kills the SOCKET after N stream lines (no
    terminating chunk) — the mid-stream death the gateway must turn
    into an error line, never a hang.  ``lines_a_chunk``: how many
    lines one chunk (one write) carries, as a replica's handler that
    found a whole hand-off on its stream sends them (PR 59).  A
    ``resume`` in the body starts the stream after the delivered
    prefix (``resumes`` keeps every such payload), and ``sever_after``
    then counts this response's own lines."""

    def __init__(self, lines=3, shed=None, sever_after=None,
                 lines_a_chunk=1):
        self.lines = lines
        self.shed = shed
        self.sever_after = sever_after
        self.lines_a_chunk = lines_a_chunk
        self.requests = 0
        self.resumes = []
        self.trace_ids = []
        self.held = []
        outer = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps({"queue_depth": 0,
                                   "kvcache": {"nodes": 1}}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                outer.requests += 1
                tid = self.headers.get("X-DWT-Trace-Id")
                if tid:
                    outer.trace_ids.append(tid)
                outer.held.append(self.headers.get(GATEWAY_HELD_HEADER))
                body = self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                resume, start = json.loads(body or b"{}").get("resume"), 0
                if resume is not None:
                    outer.resumes.append(resume)
                    start = len(resume["delivered_tokens"])
                if outer.shed is not None:
                    body = json.dumps({"error": "replica saturated"}
                                      ).encode()
                    self.send_response(outer.shed)
                    self.send_header("Retry-After", "7")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                self.send_response(200)
                self.send_header("Content-Type", "application/jsonl")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def chunk(data):
                    self.wfile.write(f"{len(data):x}\r\n".encode())
                    self.wfile.write(data + b"\r\n")

                held = b""
                for i in range(start, outer.lines):
                    if (outer.sever_after is not None
                            and i - start >= outer.sever_after):
                        self.wfile.flush()
                        # a real FIN, not just a dropped handle (the
                        # handler's buffered files keep the fd alive):
                        # the peer sees EOF with NO terminating chunk
                        self.close_connection = True
                        self.connection.shutdown(socket.SHUT_RDWR)
                        return
                    held += json.dumps({"step": i, "tokens": [100 + i]}
                                       ).encode() + b"\n"
                    if ((i - start + 1) % outer.lines_a_chunk == 0
                            or i == outer.lines - 1):
                        chunk(held)
                        held = b""
                chunk(b"")

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.host, self.port = self.httpd.server_address
        self.rid = f"{self.host}:{self.port}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _dead_endpoint():
    """A (host, port) nothing listens on — connects are refused fast."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return "127.0.0.1", port


def _post_stream(host, port, body, timeout=60):
    """POST /generate with stream=True; returns (status, headers,
    parsed JSONL lines, truncated_flag)."""
    conn = HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/generate", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        headers = dict(resp.getheaders())
        if resp.status != 200:
            return resp.status, headers, [json.loads(resp.read())], False
        lines, truncated = [], False
        try:
            while True:
                ln = resp.readline()
                if not ln:
                    break
                ln = ln.strip()
                if ln:
                    lines.append(json.loads(ln))
        except Exception:
            truncated = True
        return resp.status, headers, lines, truncated
    finally:
        conn.close()


def _gateway(replicas, *, retry_limit=1, resume_limit=1, sustain=3,
             min_prefix=8, block_tokens=8, start_prober=False,
             cooldown=60.0):
    registry = ReplicaRegistry(replicas, sustain=sustain,
                               readmit_cooldown_s=cooldown,
                               probe_interval_s=0.2)
    router = PrefixAwareRouter(registry, min_prefix_tokens=min_prefix,
                               block_tokens=block_tokens)
    gw = GatewayHTTPServer(registry, router, port=0,
                           retry_limit=retry_limit,
                           resume_limit=resume_limit)
    if start_prober:
        gw.start()
    else:
        # http thread only: tests drive the debounce deterministically
        threading.Thread(target=gw.httpd.serve_forever,
                         daemon=True).start()
    return gw


@pytest.mark.quick
def test_retry_before_first_token_on_a_dead_replica():
    stub = _StubReplica(lines=3)
    dead = _dead_endpoint()
    gw = _gateway([dead, (stub.host, stub.port)])
    try:
        toks = list(range(2, 18))
        # teach the router the DEAD replica holds this prefix
        gw.router.record(f"{dead[0]}:{dead[1]}", toks)
        st, headers, lines, truncated = _post_stream(
            gw.host, gw.port, {"prompt_ids": [toks],
                               "max_new_tokens": 3, "stream": True})
        assert st == 200 and not truncated
        assert [d["tokens"][0] for d in lines] == [100, 101, 102]
        # the retry landed on the live stub, and the client can see it
        assert headers["X-DWT-Replica"] == stub.rid
        assert stub.requests == 1
        # the dead replica took a strike on the shared streak
        assert gw.registry.get(f"{dead[0]}:{dead[1]}").fail_streak >= 1
    finally:
        gw.shutdown()
        stub.close()


@pytest.mark.quick
def test_replica_shed_propagates_with_retry_after_and_no_retry():
    shedding = _StubReplica(shed=503)
    healthy = _StubReplica(lines=2)
    gw = _gateway([(shedding.host, shedding.port),
                   (healthy.host, healthy.port)])
    try:
        toks = list(range(2, 18))
        gw.router.record(shedding.rid, toks)
        st, headers, lines, _ = _post_stream(
            gw.host, gw.port, {"prompt_ids": [toks],
                               "max_new_tokens": 3, "stream": True})
        # federated admission: the replica's own 503 is the answer —
        # Retry-After propagates verbatim, no second replica is tried
        assert st == 503
        assert headers["Retry-After"] == "7"
        assert "saturated" in lines[0]["error"]
        assert healthy.requests == 0
    finally:
        gw.shutdown()
        shedding.close()
        healthy.close()


@pytest.mark.quick
def test_gateway_sheds_503_when_every_candidate_is_dead():
    gw = _gateway([_dead_endpoint(), _dead_endpoint()], retry_limit=2)
    try:
        st, headers, lines, _ = _post_stream(
            gw.host, gw.port, {"prompt_ids": [list(range(2, 18))],
                               "max_new_tokens": 3, "stream": True})
        assert st == 503
        assert "Retry-After" in headers
        assert "every candidate replica" in lines[0]["error"]
    finally:
        gw.shutdown()


@pytest.mark.quick
def test_midstream_socket_death_becomes_error_line_not_a_hang():
    severing = _StubReplica(lines=5, sever_after=2)
    gw = _gateway([(severing.host, severing.port)], sustain=1)
    try:
        st, _, lines, _ = _post_stream(
            gw.host, gw.port, {"prompt_ids": [list(range(2, 18))],
                               "max_new_tokens": 5, "stream": True},
            timeout=30)
        # first token was forwarded, so no retry: the delivered prefix
        # plus ONE error line, framing intact, stream terminated
        assert st == 200
        assert [d["tokens"][0] for d in lines[:2]] == [100, 101]
        assert "error" in lines[-1]
        assert severing.rid in lines[-1]["error"]
        # the mid-stream death struck the replica out of routing
        assert not gw.registry.is_up(severing.rid)
    finally:
        gw.shutdown()
        severing.close()


@pytest.mark.quick
@pytest.mark.parametrize("sever_after", [None, 4, 8],
                         ids=["whole", "killed_after_chunk_1",
                              "killed_after_chunk_2"])
def test_a_replica_that_sends_several_lines_a_chunk(sever_after):
    """A replica's handler writes a hand-off's lines as one chunk
    (PR 59).  The relay reads lines, not chunks: every line is forwarded
    once and as a chunk of its own, the journal counts every token, and
    a replica killed between two chunks is resumed from the token
    behind the last chunk (docs/DESIGN.md section 23)."""
    first = _StubReplica(lines=10, lines_a_chunk=4,
                         sever_after=sever_after)
    survivor = _StubReplica(lines=10, lines_a_chunk=4)
    gw = _gateway([(first.host, first.port),
                   (survivor.host, survivor.port)], sustain=1)
    try:
        toks = list(range(2, 18))
        gw.router.record(first.rid, toks)
        st, _, lines, truncated = _post_stream(
            gw.host, gw.port, {"prompt_ids": [toks],
                               "max_new_tokens": 10, "stream": True},
            timeout=30)
        assert st == 200 and not truncated
        assert lines == [{"step": i, "tokens": [100 + i]}
                         for i in range(10)]
        assert first.requests == 1 and not first.resumes
        if sever_after is None:
            assert survivor.requests == 0
        else:
            assert survivor.resumes == [{
                "delivered_tokens": [100 + i for i in range(sever_after)],
                "rng_step_offset": sever_after}]
    finally:
        gw.shutdown()
        first.close()
        survivor.close()


@pytest.mark.quick
def test_gateway_metrics_debugz_and_trace_surfaces():
    stub = _StubReplica(lines=2)
    gw = _gateway([(stub.host, stub.port)], start_prober=True)
    try:
        toks = list(range(2, 18))
        for _ in range(2):
            st, _, _, _ = _post_stream(
                gw.host, gw.port, {"prompt_ids": [toks],
                                   "max_new_tokens": 2, "stream": True})
            assert st == 200
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        for name in ("dwt_gateway_prefix_routed_requests_total",
                     "dwt_gateway_hashed_requests_total",
                     "dwt_gateway_retried_requests_total",
                     "dwt_gateway_shed_requests_total",
                     "dwt_gateway_replica_down_total",
                     "dwt_gateway_replica_up_total",
                     "dwt_gateway_up_replicas",
                     "dwt_gateway_proxy_ttft_seconds"):
            assert name in text, name
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        conn.request("GET", "/debugz")
        dz = json.loads(conn.getresponse().read())
        conn.close()
        assert stub.rid in dz["routing"]["replicas"]
        row = dz["routing"]["replicas"][stub.rid]
        assert row["routed"] == 2 and row["up"] is True
        assert row["index_entries"] >= 1
        assert dz["registry"]["replicas"][stub.rid]["fail_streak"] == 0
        # one trace id covered gateway -> replica: the replica saw the
        # header, and the gateway's /trace holds route + proxy spans
        assert len(stub.trace_ids) == 2
        # ... beside it the seconds the gateway held each request before
        # it forwarded it (body read, routing): a duration, so it means
        # the same on the replica's host
        assert len(stub.held) == 2
        assert all(0 < float(h) < 10 for h in stub.held)
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        conn.request("GET", "/trace")
        tr = json.loads(conn.getresponse().read())
        conn.close()
        names = {ev["name"] for ev in tr["traceEvents"]}
        assert {"gateway.route", "gateway.proxy"} <= names
    finally:
        gw.shutdown()
        stub.close()


@pytest.mark.quick
def test_drain_endpoint_flips_routing_and_keeps_proxying(params=None):
    """POST /drain: the drained stub stops receiving NEW requests (they
    all land on the other replica) while /health degrades gracefully
    and /debugz names the drained replica; undrain restores it."""
    stubs = [_StubReplica(lines=2), _StubReplica(lines=2)]
    gw = _gateway([(s.host, s.port) for s in stubs], min_prefix=8,
                  block_tokens=8)
    try:
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        conn.request("POST", "/drain", body=json.dumps(
            {"replica": stubs[0].rid}))
        resp = conn.getresponse()
        out = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        assert out["draining"] is True
        assert out["routable"] == [stubs[1].rid]
        # unknown replica: 400, names the fleet
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        conn.request("POST", "/drain", body=json.dumps(
            {"replica": "nope:1"}))
        resp = conn.getresponse()
        assert resp.status == 400
        assert "replicas" in json.loads(resp.read())
        conn.close()
        # every generate lands on the OTHER stub
        before = stubs[0].requests
        for i in range(6):
            st, headers, _, _ = _post_stream(
                gw.host, gw.port,
                {"prompt_ids": [list(range(2, 18)) + [i]],
                 "max_new_tokens": 2, "stream": True})
            assert st == 200
            assert headers["X-DWT-Replica"] == stubs[1].rid
        assert stubs[0].requests == before
        # surfaced: /health stays ok (one routable), /debugz names it
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        conn.request("GET", "/health")
        health = json.loads(conn.getresponse().read())
        conn.close()
        assert health["status"] == "ok"
        assert health["replicas_routable"] == 1
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        conn.request("GET", "/debugz")
        dbg = json.loads(conn.getresponse().read())
        conn.close()
        assert dbg["registry"]["replicas"][stubs[0].rid]["draining"]
        # undrain restores routing
        conn = HTTPConnection(gw.host, gw.port, timeout=10)
        conn.request("POST", "/drain", body=json.dumps(
            {"replica": stubs[0].rid, "draining": False}))
        resp = conn.getresponse()
        assert json.loads(resp.read())["draining"] is False
        conn.close()
        assert set(gw.registry.routable_replicas()) == {
            stubs[0].rid, stubs[1].rid}
    finally:
        gw.shutdown()
        for s in stubs:
            s.close()


# ---------------------------------------------------------------------------
# loopback soak: real replicas, real engines
# ---------------------------------------------------------------------------

def _engine(params, **kw):
    kw.setdefault("max_seq", 96)
    kw.setdefault("max_batch", 2)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("kv_cache_blocks", 0)
    kw.setdefault("kv_block_tokens", 8)
    return ContinuousBatchingEngine(CFG, params, **kw)


# tier-1 budget: the routing decision tables + proxy tests keep the
# quick-lane reps; the three-replica soak rides the slow lane
@pytest.mark.slow
def test_loopback_soak_three_replicas_cache_aware(params):
    """The -m quick representative of the gateway soak: three real
    replicas, grouped shared-prefix workload, every answer bit-identical
    to the replica's own direct answer, groups sticking to one replica
    after the first member."""
    engines = [_engine(params) for _ in range(3)]
    servers = []
    for eng in engines:
        srv = InferenceHTTPServer(eng, port=0)
        srv.start()
        servers.append(srv)
    gw = _gateway([(s.host, s.port) for s in servers], min_prefix=8,
                  block_tokens=8, start_prober=True)
    try:
        rng = np.random.default_rng(3)
        groups = [list(rng.integers(2, CFG.vocab_size - 1, 16))
                  for _ in range(2)]
        served = {}       # group index -> replica rid
        outputs = {}
        for round_i in range(3):
            for g, prefix in enumerate(groups):
                toks = [int(t) for t in prefix] + [2 + g, 3 + round_i]
                st, headers, lines, truncated = _post_stream(
                    gw.host, gw.port,
                    {"prompt_ids": [toks], "max_new_tokens": 4,
                     "stream": True}, timeout=300)
                assert st == 200 and not truncated
                rid = headers["X-DWT-Replica"]
                served.setdefault(g, rid)
                # after the first member, the group STICKS
                assert rid == served[g], (g, round_i)
                outputs[tuple(toks)] = [d["tokens"][0] for d in lines]
        # bit-identity through the gateway hop: re-ask the replica
        # directly for one prompt per group
        for g, prefix in enumerate(groups):
            toks = [int(t) for t in prefix] + [2 + g, 3]
            host, port = served[g].split(":")
            st, _, lines, _ = _post_stream(
                host, int(port), {"prompt_ids": [toks],
                                  "max_new_tokens": 4, "stream": True},
                timeout=300)
            assert st == 200
            assert [d["tokens"][0] for d in lines] == outputs[tuple(toks)]
        # the routing split is observable: first member hashed, the
        # rest prefix-routed
        table = gw.router.routing_table()["replicas"]
        assert sum(r["prefix_routed"] for r in table.values()) >= 4
        # replica-side evidence: warm prefixes were actually reused
        reused = sum(e.stats()["kvcache"]["partial_hit_tokens"]
                     for e in engines)
        assert reused > 0
    finally:
        gw.shutdown()
        for srv, eng in zip(servers, engines):
            srv.shutdown()
            eng.close()


class _CrashyBackend:
    """Wrap an engine so its token stream consults a comm/faults
    FaultPlan: the crash_after rule raises InjectedCrash mid-stream,
    modeling a replica process dying between decode steps.  It counts
    steps, so it reads the engine's stream a step at a time and, asked
    for what is ready (``all_ready``, PR 59), says one step."""

    def __init__(self, inner, plan, rid):
        self._inner = inner
        self._plan = plan
        self._rid = rid

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def generate_stream(self, *a, all_ready=False, **kw):
        for item in self._inner.generate_stream(*a, **kw):
            ev = self._plan.on_recv(self._rid)
            if ev is not None:
                raise InjectedCrash(
                    f"{self._rid}: injected crash_after (seq "
                    f"{ev.get('seq')})")
            yield [item] if all_ready else item


def test_midstream_replica_kill_chaos_injected_crash(params):
    """A replica dies mid-stream via a seeded comm/faults crash rule
    with resume DISABLED (--resume-limit 0): the client holds the
    delivered prefix plus an error line (never a hang, never divergent
    tokens), and a follow-up request completes the same greedy answer
    in full on the fleet.  This pins the documented post-resume
    fallback contract; the resume path itself is pinned in
    test_stream_failover.py."""
    plan = FaultPlan(seed=7, rules=[FaultRule(kind="crash_after",
                                              n_msgs=3, max_count=1)])
    engines = [_engine(params) for _ in range(2)]
    servers = []
    for i, eng in enumerate(engines):
        backend = (_CrashyBackend(eng, plan, "replica0") if i == 0
                   else eng)
        srv = InferenceHTTPServer(backend, port=0)
        srv.start()
        servers.append(srv)
    gw = _gateway([(s.host, s.port) for s in servers], min_prefix=8,
                  block_tokens=8, resume_limit=0)
    try:
        toks = list(range(2, 18))
        crashy_rid = f"{servers[0].host}:{servers[0].port}"
        gw.router.record(crashy_rid, toks)
        st, _, lines, _ = _post_stream(
            gw.host, gw.port, {"prompt_ids": [toks],
                               "max_new_tokens": 8, "stream": True},
            timeout=300)
        # the crash fired after 3 streamed steps: delivered prefix +
        # the replica's own error line, forwarded with framing intact
        assert st == 200
        assert "error" in lines[-1] and "injected" in lines[-1]["error"]
        delivered = [d["tokens"][0] for d in lines[:-1]]
        assert len(delivered) == 3
        assert [e["kind"] for e in plan.events] == ["crash_after"]
        # the fleet still answers, and the full greedy stream extends
        # exactly the delivered prefix (bit-identity across the kill)
        st, _, lines, truncated = _post_stream(
            gw.host, gw.port, {"prompt_ids": [toks],
                               "max_new_tokens": 8, "stream": True},
            timeout=300)
        assert st == 200 and not truncated
        full = [d["tokens"][0] for d in lines]
        assert len(full) == 8
        assert full[:3] == delivered
    finally:
        gw.shutdown()
        for srv, eng in zip(servers, engines):
            srv.shutdown()
            eng.close()


@pytest.mark.quick
@pytest.mark.parametrize("stream", [False, True])
def test_replica_echoes_trace_header_on_generate(params, stream):
    """The http_server seam: a proxied /generate carries
    X-DWT-Trace-Id, and the replica echoes it on blocking AND
    streaming responses (one trace id covers gateway -> replica);
    the seconds the gateway says it held the request land in the
    replica's request-path record as the first part of the row, and a
    direct request books none."""
    eng = _engine(params)
    srv = InferenceHTTPServer(eng, port=0)
    srv.start()
    try:
        for proxied in (True, False):
            conn = HTTPConnection(srv.host, srv.port, timeout=300)
            conn.request("POST", "/generate", body=json.dumps(
                {"prompt_ids": [list(range(2, 10))],
                 "max_new_tokens": 2, "stream": stream}),
                headers={"Content-Type": "application/json",
                         **({"X-DWT-Trace-Id": "00ab00ab00ab00ab",
                             GATEWAY_HELD_HEADER: "0.125000"}
                            if proxied else {})})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("X-DWT-Trace-Id") == (
                "00ab00ab00ab00ab" if proxied else None)
            resp.read()
            conn.close()
            path = srv.request_path.snapshot()
            t_gateway, t_accept = path["recent"][-1][:2]
            assert path["gateway_s"] == pytest.approx(0.125)
            assert t_accept - t_gateway == pytest.approx(
                0.125 if proxied else 0.0, abs=2e-5)
        assert path["ingress_count"] == 2
        assert [r[5] for r in path["recent"]] == [int(stream)] * 2
    finally:
        srv.shutdown()
        eng.close()
