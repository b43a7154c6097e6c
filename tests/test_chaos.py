"""Chaos soak: deterministic fault injection against the elastic pipeline.

The ISSUE-5 acceptance invariants, pinned:

- under a seeded fault plan (drop + delay + duplicate + corrupt + worker
  crash) on a 3-stage loopback elastic pipeline, the greedy token stream
  after recovery is BIT-IDENTICAL to the fault-free run;
- zero leaked KV slots after every crash/reshard;
- a corrupt frame is detected by CRC (never decoded into a wrong token)
  with ``dwt_transport_corrupt_frames_total`` incremented;
- a postmortem bundle is written naming the injected fault;
- same seed + same plan ⇒ byte-identical injected-fault event sequence;
- ``--fault-plan`` is rejected outside ``--chaos`` mode;
- stale-epoch frames (delayed/duplicated pre-reshard traffic) are
  dropped and can never satisfy a newer reshard's ack-wait;
- overload shedding: a full admission queue answers 503 + Retry-After;
  ``--request-timeout`` cancels instead of hanging.

A fast deterministic subset runs in tier-1; the randomized multi-seed
soak is ``@slow``.
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

import jax

from distributed_inference_demo_tpu.comm import wire
from distributed_inference_demo_tpu.comm.faults import (
    FaultConfigError, FaultPlan, FaultRule, FaultyTransport, InjectedCrash,
    load_fault_plan, maybe_wrap)
from distributed_inference_demo_tpu.comm.transport import (
    LoopbackNetwork, LoopbackTransport, TransportTimeout)
from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.base import split_layer_ranges
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.elastic import (
    ElasticHeader, ElasticStageRuntime, ElasticWorker)
from distributed_inference_demo_tpu.telemetry import catalog, postmortem
from distributed_inference_demo_tpu.telemetry.flightrecorder import (
    FlightRecorder, set_flight_recorder)
from distributed_inference_demo_tpu.telemetry.postmortem import (
    PostmortemWriter)

GREEDY = SamplingParams(greedy=True)
PROMPT = np.array([[5, 17, 42, 7, 99, 3, 12, 56]], dtype=np.int32)
MODEL = "llama-test"


@pytest.fixture(autouse=True)
def _isolate_globals():
    set_flight_recorder(None)
    postmortem.set_postmortem_writer(None)
    yield
    set_flight_recorder(None)
    postmortem.set_postmortem_writer(None)


def _counter_value(c, **labels) -> float:
    want = tuple(sorted(labels.items()))
    for _name, lab, value in c.samples():
        if tuple(sorted(lab)) == want:
            return value
    return 0.0


_REFERENCE_MEMO = {}


def reference_tokens(prompt, max_new):
    """Memoized per (prompt, max_new): several tests pin against the
    same fault-free stream, and each cold call costs an engine build."""
    prompt = np.asarray(prompt)
    key = (prompt.tobytes(), prompt.shape, max_new)
    if key not in _REFERENCE_MEMO:
        cfg = get_model_config(MODEL)
        params = init_full_params(jax.random.PRNGKey(0), cfg)
        _REFERENCE_MEMO[key] = InferenceEngine(
            cfg, params, max_seq=64, sampling=GREEDY).generate(
            prompt, max_new).tokens
    return _REFERENCE_MEMO[key]


# ---------------------------------------------------------------------------
# fault-plan unit behavior


def test_fault_plan_spec_roundtrip_and_validation():
    spec = {"seed": 99, "name": "soak", "rules": [
        {"kind": "delay", "peer": "s1", "tag_prefix": "h:", "prob": 0.25,
         "delay_ms": 5},
        {"kind": "corrupt", "after": 2, "max_count": 1},
        {"kind": "crash_after", "n_msgs": 10}]}
    plan = FaultPlan.from_spec(spec)
    assert plan.to_spec() == spec
    assert FaultPlan.from_json(json.dumps(spec)).to_spec() == spec
    with pytest.raises(FaultConfigError, match="unknown fault kind"):
        FaultPlan.from_spec({"rules": [{"kind": "nuke"}]})
    with pytest.raises(FaultConfigError, match="n_msgs"):
        FaultPlan.from_spec({"rules": [{"kind": "crash_after"}]})
    with pytest.raises(FaultConfigError, match="unknown fields"):
        FaultPlan.from_spec({"rules": [{"kind": "drop", "probe": 1}]})
    with pytest.raises(FaultConfigError, match="valid JSON"):
        FaultPlan.from_json("{nope")


def _drive(seed: int) -> list:
    """One fixed message sequence through a probabilistic plan."""
    plan = FaultPlan(seed=seed, rules=[
        FaultRule(kind="drop", prob=0.3),
        FaultRule(kind="delay", prob=0.4, delay_ms=1),
        FaultRule(kind="corrupt", prob=0.2)])
    net = LoopbackNetwork()
    t = FaultyTransport(LoopbackTransport("a", net), plan)
    LoopbackTransport("b", net)
    for i in range(64):
        t.send("b", f"h:{i % 7}:{i}", bytes(16 + i))
    return plan.events


def test_injected_faults_are_flight_recorded():
    """Every injected fault lands in the flight ring as a
    ``fault_injected`` event carrying the rule kind as ``fault_kind`` —
    the postmortem analyzer's evidence that a chaos bundle can name its
    own cause."""
    rec = FlightRecorder(max_events=64)
    set_flight_recorder(rec)
    plan = FaultPlan(seed=3, rules=[
        FaultRule(kind="drop", tag_prefix="h:0:0"),
        FaultRule(kind="partition", peer="b", tag_prefix="h:0:1")])
    net = LoopbackNetwork()
    t = FaultyTransport(LoopbackTransport("a", net), plan)
    LoopbackTransport("b", net)
    t.send("b", "h:0:0", b"x")          # dropped
    t.send("b", "h:0:1", b"y")          # partition activates (1st casualty)
    t.send("b", "h:0:2", b"z")          # swallowed by the partition
    got = [e for e in rec.snapshot() if e["kind"] == "fault_injected"]
    kinds = [e["fault_kind"] for e in got]
    assert "drop" in kinds and "partition" in kinds, kinds
    assert "partition_drop" in kinds, kinds
    assert all(e["device"] == "a" for e in got)


def test_same_seed_same_plan_identical_event_sequence():
    """Determinism is itself asserted: same seed + same plan + same
    message sequence ⇒ byte-identical injected-fault event sequence
    (the replay-from-postmortem-by-seed property)."""
    e1, e2 = _drive(1234), _drive(1234)
    assert e1, "plan injected nothing — the drive is too short"
    assert json.dumps(e1) == json.dumps(e2)
    assert json.dumps(_drive(99)) != json.dumps(e1)  # the seed matters


def test_fault_kinds_apply_on_the_wire():
    plan = FaultPlan(seed=0, rules=[
        FaultRule(kind="drop", tag_prefix="d:"),
        FaultRule(kind="duplicate", tag_prefix="u:"),
        FaultRule(kind="corrupt", tag_prefix="c:"),
        FaultRule(kind="reorder", tag_prefix="r:", max_count=1),
        FaultRule(kind="partition", peer="b", tag_prefix="p:")])
    net = LoopbackNetwork()
    fa = FaultyTransport(LoopbackTransport("a", net), plan)
    b = LoopbackTransport("b", net)

    fa.send("b", "d:1", b"dropped")
    fa.send("b", "u:1", b"dup")
    assert b.recv("u:1", timeout=2) == b"dup"
    assert b.recv("u:1", timeout=2) == b"dup"      # the duplicate
    fa.send("b", "c:1", b"payload")
    assert b.recv("c:1", timeout=2) != b"payload"  # corrupted in flight
    fa.send("b", "r:1", b"first")                  # held back
    fa.send("b", "x:1", b"second")                 # overtakes
    tag, _ = b.recv_any(timeout=2)
    assert tag == "x:1"
    assert b.recv("r:1", timeout=2) == b"first"    # released after
    with pytest.raises(TransportTimeout):
        b.recv("d:1", timeout=0.1)
    fa.send("b", "p:1", b"partitioned")            # activates partition
    fa.send("b", "anything", b"also dead")         # peer b is gone now
    with pytest.raises(TransportTimeout):
        b.recv_any(timeout=0.1)
    kinds = [e["kind"] for e in plan.events]
    for k in ("drop", "duplicate", "corrupt", "reorder", "partition",
              "partition_drop"):
        assert k in kinds, kinds


def test_crash_after_counts_sends_and_recvs():
    plan = FaultPlan(seed=0, rules=[
        FaultRule(kind="crash_after", n_msgs=3)])
    net = LoopbackNetwork()
    fa = FaultyTransport(LoopbackTransport("a", net), plan)
    b = LoopbackTransport("b", net)
    fa.send("b", "t", b"1")
    fa.send("b", "t", b"2")
    b.send("a", "t", b"3")
    assert fa.recv("t", timeout=2) == b"3"     # message 3: at the limit
    with pytest.raises(InjectedCrash):
        fa.send("b", "t", b"4")
    with pytest.raises(InjectedCrash):         # dead stays dead
        fa.send("b", "t", b"5")


def test_fault_plan_rejected_without_chaos(monkeypatch):
    spec = '{"seed": 1, "rules": [{"kind": "drop"}]}'
    with pytest.raises(FaultConfigError, match="--chaos"):
        load_fault_plan(spec, chaos=False)
    # the env var alone must be rejected the same way
    monkeypatch.setenv("DWT_FAULT_PLAN", spec)
    with pytest.raises(FaultConfigError, match="--chaos"):
        load_fault_plan("", chaos=False)
    assert load_fault_plan("", chaos=True).seed == 1
    monkeypatch.delenv("DWT_FAULT_PLAN")
    assert load_fault_plan("", chaos=False) is None   # off by default
    t = LoopbackTransport("a", LoopbackNetwork())
    assert maybe_wrap(t, None) is t


def test_serve_cli_rejects_fault_plan_without_chaos(capsys):
    from distributed_inference_demo_tpu import cli
    rc = cli.main(["serve", "--model", MODEL, "--fault-plan",
                   '{"seed": 1, "rules": []}'])
    assert rc == 1
    assert "--chaos" in capsys.readouterr().err
    # --chaos without --chain: the plan has no transport to fault
    rc = cli.main(["serve", "--model", MODEL, "--chaos", "--fault-plan",
                   '{"seed": 1, "rules": []}'])
    assert rc == 1
    assert "--chain" in capsys.readouterr().err


def test_worker_cli_rejects_fault_plan_without_chaos(capsys):
    from distributed_inference_demo_tpu.runtime import worker_main
    rc = worker_main.main([
        "--model", MODEL, "--stage-id", "1", "--num-stages", "2",
        "--layer-start", "0", "--layer-end", "2", "--device-id", "w",
        "--port", "0", "--header", "h@127.0.0.1:1",
        "--fault-plan", '{"seed": 1, "rules": []}'])
    assert rc == 1
    assert "--chaos" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# wire integrity on the ring


def test_corrupt_frame_detected_not_decoded():
    """A flipped byte raises WireIntegrityError out of BOTH codecs; the
    drop bookkeeping increments dwt_transport_corrupt_frames_total."""
    from distributed_inference_demo_tpu.comm import native_codec
    from distributed_inference_demo_tpu.comm.transport import (
        record_corrupt_frame)
    blob = wire.serialize_tensors([np.arange(8, dtype=np.float32)])
    bad = bytearray(blob)
    bad[-3] ^= 0x10
    with pytest.raises(wire.WireIntegrityError):
        wire.deserialize_tensors(bytes(bad))
    if native_codec.available():
        with pytest.raises(wire.WireIntegrityError):
            native_codec.deserialize_tensors(bytes(bad))
    before = _counter_value(catalog.TRANSPORT_CORRUPT_FRAMES)
    try:
        wire.deserialize_tensors(bytes(bad))
    except wire.WireIntegrityError as e:
        record_corrupt_frame("s1", "h:0:0", len(bad), e)
    assert _counter_value(catalog.TRANSPORT_CORRUPT_FRAMES) == before + 1


def test_zero_checksum_frames_from_old_peers_accepted():
    blob = wire.serialize_tensors([np.arange(4, dtype=np.int32)],
                                  checksum=False)
    assert blob[6:8] == b"\x00\x00"
    msg = wire.deserialize_tensors(blob)
    np.testing.assert_array_equal(msg.tensors[0], np.arange(4))


def test_worker_drops_corrupt_frame_without_forwarding():
    """The stage-level contract: a corrupt hidden chunk is counted and
    dropped — no forward, no sample, no cache write."""
    cfg = get_model_config(MODEL)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, 2)
    net = LoopbackNetwork()
    t0 = LoopbackTransport("s0", net)
    t1 = LoopbackTransport("s1", net)
    worker = ElasticWorker(
        ElasticStageRuntime(cfg, specs[1], full, 64, GREEDY), t1,
        next_id=None, header_id="s0", step_timeout=5)
    good = wire.serialize_tensors(
        [np.zeros((1, 4, cfg.hidden_size), np.float32)])
    bad = bytearray(good)
    bad[40] ^= 0xFF
    before = _counter_value(catalog.TRANSPORT_CORRUPT_FRAMES)
    assert worker.handle_message("h:0:0", bytes(bad)) is True
    assert worker.rt._tables == {}              # nothing ran
    with pytest.raises(TransportTimeout):      # nothing was forwarded
        t0.recv_any(timeout=0.1)
    assert _counter_value(catalog.TRANSPORT_CORRUPT_FRAMES) == before + 1
    # the same frame uncorrupted runs fine (the worker is not poisoned)
    assert worker.handle_message("h:0:0", good) is True
    assert t0.recv_any(timeout=5)[0].startswith("tok:0:0")


# ---------------------------------------------------------------------------
# the chaos soak itself


class _CrashingWorker(ElasticWorker):
    """Serve loop that dies (thread exit) on InjectedCrash — a real
    worker process would die the same way via the crash handler."""

    def serve_forever(self, idle_timeout=None):
        try:
            super().serve_forever(idle_timeout)
        except InjectedCrash:
            return


def _build_chaos(num_stages, plan, faulty, max_seq=64, step_timeout=30,
                 stall_reshard_timeout=1.0):
    """Elastic loopback pipeline; transports of ids in ``faulty`` are
    wrapped with ``plan``.  Returns (header, workers, threads, ids)."""
    cfg = get_model_config(MODEL)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, num_stages)
    net = LoopbackNetwork()
    ids = [f"s{i}" for i in range(num_stages)]
    transports = [LoopbackTransport(d, net) for d in ids]
    if plan is not None:
        for i, d in enumerate(ids):
            if d in faulty:
                transports[i] = FaultyTransport(transports[i], plan)
    header = ElasticHeader(
        ElasticStageRuntime(cfg, specs[0], full, max_seq, GREEDY),
        transports[0], chain=ids, step_timeout=step_timeout,
        poll_interval=0.05,
        stall_reshard_timeout=stall_reshard_timeout)
    workers = [
        _CrashingWorker(
            ElasticStageRuntime(cfg, specs[i], full, max_seq, GREEDY),
            transports[i],
            next_id=ids[i + 1] if i + 1 < num_stages else None,
            header_id=ids[0], step_timeout=step_timeout)
        for i in range(1, num_stages)]
    threads = [threading.Thread(target=w.serve_forever, args=(30,),
                                daemon=True) for w in workers]
    for t in threads:
        t.start()
    return header, workers, threads, ids


def _supervise(header, threads, ids):
    """Heartbeat stand-in: signal failure for any worker whose serve
    thread died (the sweeper-driven path is pinned in test_elastic)."""
    stop = threading.Event()

    def watch():
        reported = set()
        while not stop.is_set():
            for wid, t in zip(ids[1:], threads):
                if not t.is_alive() and wid not in reported:
                    reported.add(wid)
                    header.signal_failure(wid)
            stop.wait(0.05)

    t = threading.Thread(target=watch, daemon=True)
    t.start()
    return stop


def _assert_no_kv_leaks(header, workers, threads):
    assert header.rt._tables == {}, "header leaked KV slots"
    # the ``end`` frees ride the chain asynchronously: give survivors a
    # bounded moment to process them before calling a slot leaked
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if all(w.rt._tables == {} for w, t in zip(workers, threads)
               if t.is_alive()):
            break
        time.sleep(0.05)
    for w, t in zip(workers, threads):
        if t.is_alive():       # survivors only; the crashed one is gone
            assert w.rt._tables == {}, (
                f"{w.transport.device_id} leaked KV slots")


@pytest.mark.slow
def test_chaos_recovery_bit_identical(tmp_path):
    """THE acceptance scenario: drop + delay + duplicate + corrupt +
    worker crash on a 3-stage loopback elastic pipeline; after recovery
    the greedy stream is bit-identical to the fault-free run, no KV slot
    leaks anywhere, and a postmortem bundle names the injected fault.

    Recovery exercises BOTH reshard paths: the corrupt/dropped frames
    stall the ring and the header reshards IN PLACE (epoch bump +
    drain/resume = retransmit); the crash kills s1's serve thread and
    the failure signal reshards it out of the chain."""
    set_flight_recorder(FlightRecorder(max_events=512))
    postmortem.set_postmortem_writer(PostmortemWriter(str(tmp_path)))
    want = reference_tokens(PROMPT, 12)

    plan = FaultPlan(seed=1234, rules=[
        # messy-but-self-healing noise on the s1 edge...
        FaultRule(kind="delay", peer="s2", tag_prefix="h:", prob=0.3,
                  delay_ms=5),
        FaultRule(kind="duplicate", peer="s2", tag_prefix="h:", prob=0.3),
        # ...one frame corrupted (CRC drops it), one dropped outright...
        FaultRule(kind="corrupt", peer="s2", tag_prefix="h:", after=2,
                  max_count=1),
        FaultRule(kind="drop", peer="s2", tag_prefix="h:", after=4,
                  max_count=1),
        # ...and then s1 dies for real
        FaultRule(kind="crash_after", n_msgs=26)])
    header, workers, threads, ids = _build_chaos(3, plan, faulty={"s1"})
    stop = _supervise(header, threads, ids)
    try:
        got = header.generate(PROMPT, 12)
    finally:
        stop.set()
    np.testing.assert_array_equal(got, want)      # bit-identical
    assert header.chain == ["s0", "s2"]           # s1 really left the ring
    kinds = {e["kind"] for e in plan.events}
    assert "crash_after" in kinds, "the crash rule never fired"
    assert "corrupt" in kinds and "drop" in kinds, kinds
    _assert_no_kv_leaks(header, workers, threads)

    # the postmortem bundle names the injected fault (analyzer included)
    bundles = postmortem.get_postmortem_writer().bundle_dirs()
    assert bundles, "no postmortem bundle written for the injected crash"
    manifests = [json.load(open(f"{b}/manifest.json")) for b in bundles]
    inj = [m for m in manifests if m["reason"] == "injected_fault_crash"]
    assert inj and inj[0]["detail"]["fault"]["kind"] == "crash_after"
    assert inj[0]["detail"]["plan_seed"] == 1234

    import importlib.util
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "postmortem_tool",
        pathlib.Path(__file__).resolve().parents[1] / "tools"
        / "postmortem.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    idx = manifests.index(inj[0])
    summary = tool.summarize_bundle(bundles[idx])
    assert summary["injected_cause"]["kind"] == "crash_after"
    assert summary["fault_plan_seed"] == 1234
    assert "INJECTED FAULT" in tool.format_summary(summary)

    header.shutdown_pipeline()
    for t in threads:
        t.join(timeout=30)


@pytest.mark.slow
def test_chaos_corrupt_frames_counted_during_recovery(tmp_path):
    """The corrupt-frame counter moves during the soak (the acceptance
    bullet: detected by CRC, counted, never a wrong token).  Slow lane
    (redundant-coverage twin): the counter-moves contract is pinned in
    tier-1 by test_corrupt_frame_detected_not_decoded and
    test_worker_drops_corrupt_frame_without_forwarding, and the soak
    recovery path by test_chaos_recovery_bit_identical."""
    set_flight_recorder(FlightRecorder(max_events=512))
    want = reference_tokens(PROMPT, 10)
    before = _counter_value(catalog.TRANSPORT_CORRUPT_FRAMES)
    plan = FaultPlan(seed=7, rules=[
        FaultRule(kind="corrupt", peer="s2", tag_prefix="h:", after=1,
                  max_count=1),
        FaultRule(kind="crash_after", n_msgs=10)])
    header, workers, threads, ids = _build_chaos(3, plan, faulty={"s1"})
    stop = _supervise(header, threads, ids)
    try:
        got = header.generate(PROMPT, 10)
    finally:
        stop.set()
    np.testing.assert_array_equal(got, want)
    assert _counter_value(catalog.TRANSPORT_CORRUPT_FRAMES) >= before + 1
    corrupt = [e for e in plan.events if e["kind"] == "corrupt"]
    assert corrupt, "the corrupt rule never fired"
    _assert_no_kv_leaks(header, workers, threads)
    header.shutdown_pipeline()
    for t in threads:
        t.join(timeout=30)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [11, 22, 33, 44, 55])
def test_chaos_soak_multi_seed(seed, tmp_path):
    """Randomized soak: probabilistic noise everywhere + a crash, five
    seeds.  The invariant never changes: bit-identical greedy stream,
    no KV leaks."""
    set_flight_recorder(FlightRecorder(max_events=512))
    want = reference_tokens(PROMPT, 16)
    plan = FaultPlan(seed=seed, rules=[
        FaultRule(kind="delay", prob=0.2, delay_ms=3),
        FaultRule(kind="duplicate", prob=0.2),
        FaultRule(kind="corrupt", tag_prefix="h:", prob=0.1),
        FaultRule(kind="drop", tag_prefix="h:", prob=0.05),
        FaultRule(kind="crash_after", n_msgs=20 + seed % 7)])
    header, workers, threads, ids = _build_chaos(3, plan, faulty={"s1"})
    stop = _supervise(header, threads, ids)
    try:
        got = header.generate(PROMPT, 16)
    finally:
        stop.set()
    np.testing.assert_array_equal(got, want)
    _assert_no_kv_leaks(header, workers, threads)
    header.shutdown_pipeline()
    for t in threads:
        t.join(timeout=30)


# ---------------------------------------------------------------------------
# elastic epoch hygiene under delay+duplicate


def test_stale_epoch_frames_dropped_property():
    """Property: for any (rid, step), an h-frame tagged with a PRE-reshard
    epoch is dropped by the worker — no compute, no cache write, no
    forward — while the current epoch's frame runs."""
    cfg = get_model_config(MODEL)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, 2)
    net = LoopbackNetwork()
    t0 = LoopbackTransport("s0", net)
    t1 = LoopbackTransport("s1", net)
    worker = ElasticWorker(
        ElasticStageRuntime(cfg, specs[1], full, 64, GREEDY), t1,
        next_id=None, header_id="s0", step_timeout=5)
    worker.epoch = 3
    frame = wire.serialize_tensors(
        [np.zeros((1, 2, cfg.hidden_size), np.float32)])
    for rid in (0, 7):
        for stale in (0, 1, 2):
            assert worker.handle_message(f"h:{rid}:0:{stale}", frame)
            assert worker.rt._tables == {}, (
                f"stale epoch {stale} frame ran (rid={rid})")
            with pytest.raises(TransportTimeout):
                t0.recv_any(timeout=0.05)
    assert worker.handle_message("h:0:0:3", frame)   # current epoch runs
    assert t0.recv_any(timeout=5)[0].startswith("tok:0:0")


def test_delayed_duplicated_stale_acks_never_satisfy_reshard():
    """The ack-wait half of epoch hygiene, driven through FaultyTransport
    delay+duplicate rules: stale-epoch ``rack`` frames — even arriving
    multiple times, late, during the newer reshard's window — never
    satisfy its ack-wait; the current epoch's ack does."""
    cfg = get_model_config(MODEL)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, 2)
    net = LoopbackNetwork()
    t0 = LoopbackTransport("s0", net)
    plan = FaultPlan(seed=5, rules=[
        FaultRule(kind="duplicate", tag_prefix="rack:"),
        FaultRule(kind="delay", tag_prefix="rack:", delay_ms=30)])
    t1 = FaultyTransport(LoopbackTransport("s1", net), plan)
    header = ElasticHeader(
        ElasticStageRuntime(cfg, specs[0], full, 64, GREEDY),
        t0, chain=["s0", "s1"], step_timeout=1.0, poll_interval=0.1)

    # stale acks (epoch 0 and a future-stale 1-off) injected through the
    # faulty transport: delayed AND duplicated, they land inside the
    # epoch-1 ack window below — and must all be ignored
    t1.send("s0", "rack:s1:0", b"")
    with pytest.raises(TransportTimeout, match="reshard acks"):
        header.reshard(["s0", "s1"])               # -> epoch 1, no valid ack
    assert [e["kind"] for e in plan.events] == ["duplicate", "delay"]

    # the current epoch's ack (epoch 2 after this reshard call bumps it),
    # also delayed+duplicated, satisfies the wait exactly once
    t1.send("s0", "rack:s1:2", b"")
    header.reshard(["s0", "s1"])
    assert header.epoch == 2


# ---------------------------------------------------------------------------
# disaggregated prefill/decode migration under faults (DESIGN.md §15)


def _build_disagg(cfg, full, prefill_plans, max_seq=64, chunk=8,
                  ack_timeout=0.5):
    """Loopback disagg deployment: coordinator + one prefill worker per
    entry of ``prefill_plans`` (its fault plan, or None) + one decode
    worker.  Prefill worker threads die on InjectedCrash like a real
    process would (the crash handler path)."""
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.runtime.disagg import (
        DecodeWorker, DisaggCoordinator, PrefillWorker)

    net = LoopbackNetwork()
    tc = LoopbackTransport("coord", net)
    pids = [f"p{i}" for i in range(len(prefill_plans))]
    engine = ContinuousBatchingEngine(
        cfg, full, max_seq=max_seq, max_batch=2, sampling=GREEDY,
        kv_cache_blocks=0)
    pws, threads = [], []
    for pid, plan in zip(pids, prefill_plans):
        t = LoopbackTransport(pid, net)
        if plan is not None:
            t = FaultyTransport(t, plan)
        pw = PrefillWorker(cfg, full, t, max_seq=max_seq,
                           prefill_chunk=chunk, ack_timeout=ack_timeout)
        pws.append(pw)

        def serve(w=pw):
            try:
                w.serve_forever()
            except InjectedCrash:
                return            # the injected death IS the scenario
        th = threading.Thread(target=serve, daemon=True)
        th.start()
        threads.append(th)
    dw = DecodeWorker(engine, LoopbackTransport("d0", net))
    dth = threading.Thread(target=dw.serve_forever, daemon=True)
    dth.start()
    coord = DisaggCoordinator(tc, pids, "d0")
    return coord, pws, dw, engine, threads, dth


def _assert_no_page_leaks(engine, pws):
    """The §15 ownership acceptance: idle ``used == tree.block_count``
    on the decode pool (tree + zero in-flight request pages) AND every
    surviving prefill pool — bounded wait for async completions."""
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        snaps = [engine.kv_cache.snapshot()] + [
            pw.kv_cache.snapshot() for pw in pws]
        if all(s["blocks_used"] == s["tree_blocks"] for s in snaps):
            return
        time.sleep(0.05)
    raise AssertionError(f"page leak: {snaps}")


def test_chaos_migration_faults_bit_identical(tmp_path):
    """The migration-tag fault plan satellite: duplicate + corrupt +
    drop scoped to page-transfer (``pg:``) frames.  The (rid, attempt,
    seq) dedup makes duplicated/retried page frames idempotent, CRC
    drops the corrupt frame before any adopt, and the ack-driven
    go-back-n retransmit refills the holes — greedy output stays
    bit-identical and neither pool leaks a page."""
    set_flight_recorder(FlightRecorder(max_events=512))
    cfg = get_model_config(MODEL)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    prompt = (np.arange(37) % 50 + 3).astype(np.int32)
    want = reference_tokens(prompt[None], 8)[0]

    plan = FaultPlan(seed=7, rules=[
        FaultRule(kind="duplicate", tag_prefix="pg:", prob=0.5),
        FaultRule(kind="corrupt", tag_prefix="pg:", after=1,
                  max_count=1),
        FaultRule(kind="drop", tag_prefix="pg:", after=3, max_count=1)])
    coord, pws, dw, engine, threads, dth = _build_disagg(cfg, full,
                                                         [plan])
    try:
        got = coord.submit(prompt, 8).wait(timeout=120)
        np.testing.assert_array_equal(got, want)     # bit-identical
        kinds = {e["kind"] for e in plan.events}
        assert {"duplicate", "corrupt", "drop"} & kinds, kinds
        # the faults actually exercised the recovery machinery
        if "corrupt" in kinds or "drop" in kinds:
            assert pws[0].stats["retransmitted_frames"] >= 1
        if "duplicate" in kinds:
            assert dw.stats["dropped_frames"] >= 1
        assert engine.kv_cache.snapshot()["h2d_bytes"] == 0
        _assert_no_page_leaks(engine, pws)
    finally:
        for pw in pws:
            pw.stop()
        dw.stop()
        coord.close()
        engine.close()


def test_chaos_prefill_crash_mid_migration_reschedules(tmp_path):
    """THE §15 chaos acceptance: a prefill worker crashes mid-migration
    (injected ``crash_after`` fires while page frames are in flight);
    the coordinator reschedules the request to the surviving worker
    under a bumped attempt, the decode worker discards the stale
    attempt's staged frames (which held ZERO pool pages), the greedy
    stream is bit-identical, the decode-side radix tree keeps its
    ownership invariant, and the postmortem bundle names the injected
    fault."""
    set_flight_recorder(FlightRecorder(max_events=512))
    postmortem.set_postmortem_writer(PostmortemWriter(str(tmp_path)))
    cfg = get_model_config(MODEL)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    prompt = (np.arange(37) % 50 + 3).astype(np.int32)
    want = reference_tokens(prompt[None], 8)[0]

    # msg 1 is the dreq receive, so the crash fires on a page-frame
    # send — genuinely mid-migration
    plan = FaultPlan(seed=1, rules=[
        FaultRule(kind="crash_after", n_msgs=2)])
    coord, pws, dw, engine, threads, dth = _build_disagg(
        cfg, full, [plan, None])
    stop = threading.Event()

    def watch():           # heartbeat stand-in (test_elastic wires the
        while not stop.is_set():     # real sweeper)
            if not threads[0].is_alive():
                coord.signal_failure("p0")
                return
            stop.wait(0.05)
    threading.Thread(target=watch, daemon=True).start()
    try:
        req = coord.submit(prompt, 8)
        got = req.wait(timeout=120)
        stop.set()
        np.testing.assert_array_equal(got, want)     # bit-identical
        assert req.attempt == 1 and req.worker == "p1"
        assert coord.stats["rescheduled"] == 1
        assert "crash_after" in {e["kind"] for e in plan.events}
        # stale attempt fully discarded; no staged residue, no pages
        assert dw._staged == {}
        # ownership invariant on the decode tree: used == tree-owned +
        # in-flight (nothing in flight after completion)
        _assert_no_page_leaks(engine, [pws[1]])

        # the postmortem bundle names the injected fault
        bundles = postmortem.get_postmortem_writer().bundle_dirs()
        assert bundles, "no postmortem bundle for the injected crash"
        manifests = [json.load(open(f"{b}/manifest.json"))
                     for b in bundles]
        inj = [m for m in manifests
               if m["reason"] == "injected_fault_crash"]
        assert inj and inj[0]["detail"]["fault"]["kind"] == "crash_after"
        assert inj[0]["detail"]["plan_seed"] == 1
    finally:
        stop.set()
        for pw in pws:
            pw.stop()
        dw.stop()
        coord.close()
        engine.close()


# ---------------------------------------------------------------------------
# overload shedding + request deadlines (graceful degradation satellites)


def _tiny_batching_engine(max_seq=64, **kw):
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    cfg = get_model_config(MODEL)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    return ContinuousBatchingEngine(
        cfg, params, max_seq=max_seq, max_batch=1, sampling=GREEDY,
        kv_cache_blocks=0, **kw)


def _wait_for(cond, timeout=60.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _hold_the_only_slot(eng):
    """A request of 1,000 tokens that keeps the engine's one slot until it
    is cancelled (PR 32; one of 56 can end between a poll and a call),
    returned once it decodes AND is no longer counted as waiting: the
    intake takes a request off its pending list a moment after it filled
    the slot, and a submit in between is shed at depth 1 (seen once under
    six workers, PR 64)."""
    blocker = eng.submit(np.arange(8, dtype=np.int32), 1000)

    def holds():
        st = eng.stats()
        return st["active_slots"] == 1 and st["queue_depth"] == 0

    _wait_for(holds, what="the blocker to take the slot")
    return blocker


def test_admission_queue_sheds_at_depth():
    from distributed_inference_demo_tpu.runtime.overload import (
        SchedulerOverloaded)
    with _tiny_batching_engine(max_seq=1100, max_queue_depth=1) as eng:
        prompt = np.arange(8, dtype=np.int32)
        r1 = _hold_the_only_slot(eng)
        r2 = eng.submit(prompt, 4)         # queued (depth 1)
        with pytest.raises(SchedulerOverloaded) as exc:
            eng.submit(prompt, 4)          # past the limit: shed
        assert exc.value.retry_after_s >= 1.0
        r1.cancel()
        r2.wait(timeout=60)                # the queued one still serves


def test_multirow_generate_shed_cancels_admitted_rows():
    """All-or-nothing admission: when row 1 of a 2-row generate() is
    shed, the already-admitted row 0 is cancelled — a 503'd request must
    not leave orphan rows burning slots while the server sheds load."""
    from distributed_inference_demo_tpu.runtime.overload import (
        SchedulerOverloaded)
    with _tiny_batching_engine(max_seq=1100, max_queue_depth=1) as eng:
        prompt = np.arange(8, dtype=np.int32)
        r1 = _hold_the_only_slot(eng)
        with pytest.raises(SchedulerOverloaded):
            eng.generate(np.stack([prompt, prompt]), 4)
        r1.cancel()
        _wait_for(lambda: (eng.stats()["queue_depth"] == 0
                           and eng.stats()["active_slots"] == 0),
                  what="the cancelled shed rows to drain, not decode")


def test_http_generate_returns_503_with_retry_after():
    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)
    with _tiny_batching_engine(max_seq=1100, max_queue_depth=1) as eng:
        srv = InferenceHTTPServer(eng, port=0)
        srv.start()
        try:
            prompt = list(range(8))
            # r1 holds the only slot until it is cancelled below, so r2
            # fills the queue and stays there whenever the HTTP request
            # lands
            r1 = _hold_the_only_slot(eng)
            r2 = eng.submit(np.arange(8, dtype=np.int32), 4)
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=30)
            conn.request("POST", "/generate", body=json.dumps(
                {"prompt_ids": [prompt], "max_new_tokens": 4}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 503
            assert int(resp.getheader("Retry-After")) >= 1
            assert "admission queue full" in body["error"]
            conn.close()
            r1.cancel()
            r2.wait(timeout=60)
        finally:
            srv.shutdown()


def test_http_request_timeout_cancels_and_returns_504():
    from distributed_inference_demo_tpu.runtime.http_server import (
        InferenceHTTPServer)
    class _Tok:          # minimal tokenizer so the stop branch is legal
        def encode(self, text):
            return [1]

        def decode(self, ids, skip_special=True):
            return "".join(f" t{int(i)}" for i in ids)

    with _tiny_batching_engine(max_seq=1100) as eng:
        srv = InferenceHTTPServer(eng, port=0, request_timeout=0.5,
                                  tokenizer=_Tok())
        srv.start()
        try:
            # occupy the single slot for far longer than the deadline
            blocker = eng.submit(np.arange(8, dtype=np.int32), 1000)
            _wait_for(lambda: eng.stats()["active_slots"] == 1,
                      what="blocker to take the slot")
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=60)
            conn.request("POST", "/generate", body=json.dumps(
                {"prompt_ids": [list(range(8))], "max_new_tokens": 4}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 504
            resp.read()
            # the stop-sequence branch honors the same deadline (it
            # rides generate_stream, a different backend path)
            conn.request("POST", "/generate", body=json.dumps(
                {"prompt_ids": [list(range(8))], "max_new_tokens": 4,
                 "stop": ["zzzz"]}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 504
            resp.read()
            conn.close()
            blocker.cancel()
            blocker.wait(timeout=60)
            # graceful: the shed request freed its queue spot; a fresh
            # request completes normally
            eng.submit(np.arange(8, dtype=np.int32), 2).wait(timeout=60)
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# §18 live-migration chaos (the ISSUE-14 acceptance): seeded faults on
# the pg:/rs: frame stream of a MID-DECODE handoff, and a source that
# crashes partway through the two-phase protocol


MIG_PROMPT = (np.arange(17) % 50 + 3).astype(np.int32)
# a LONG runway: the faulted handoff (rs: drop -> ack-timeout stall,
# corrupt/reorder -> nack rounds) takes ~0.5s, and the row must still
# be decoding when phase 2 freezes it
MIG_MAX_NEW = 480


@pytest.fixture(scope="module")
def mig_pair():
    """Two decode replicas on one loopback fabric, the target's
    migration worker serving; each test wires its own (faulty) source
    transport.  The fault-free reference stream is computed on the
    source engine itself — exact parity by construction."""
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.runtime.migration import (
        MigrationWorker)
    cfg = get_model_config(MODEL)
    params = init_full_params(jax.random.PRNGKey(0), cfg)

    def mk():
        return ContinuousBatchingEngine(
            cfg, params, max_seq=512, max_batch=2, sampling=GREEDY,
            kv_cache_blocks=80, kv_block_tokens=8)

    net = LoopbackNetwork()
    src_e, dst_e = mk(), mk()
    dst_w = MigrationWorker(dst_e, LoopbackTransport("dst", net),
                            ack_timeout=10.0)
    th = threading.Thread(target=dst_w.serve_forever, daemon=True)
    th.start()
    ref = [int(t) for t in src_e.submit(MIG_PROMPT,
                                        MIG_MAX_NEW).wait(120)]
    from types import SimpleNamespace
    yield SimpleNamespace(net=net, src_e=src_e, dst_e=dst_e,
                          dst_w=dst_w, ref=ref,
                          MigrationWorker=MigrationWorker)
    dst_w.stop()
    th.join(timeout=2)
    src_e.close()
    dst_e.close()


def _mig_no_pool_leaks(*engines):
    deadline = time.monotonic() + 5.0
    while True:
        snaps = [e.kv_cache.snapshot() for e in engines]
        if all(s["blocks_used"] == s["tree_blocks"] for s in snaps):
            return
        if time.monotonic() > deadline:
            raise AssertionError(
                "page leak: " + ", ".join(
                    f"{s['blocks_used']}/{s['tree_blocks']}"
                    for s in snaps))
        time.sleep(0.05)


def test_chaos_live_migration_faults_bit_identical(mig_pair):
    """Seeded drop + corrupt + duplicate + reorder on the pg:/rs: frame
    stream of a LIVE mid-decode handoff: the go-back-n/nack machinery
    heals every fault, the handoff still completes, and the client
    stream is bit-identical to the never-migrated run with zero pool
    pages leaked on either replica."""
    # the CPU decode can FINISH before a badly-stalled handoff freezes
    # the row — a legal local resolution; retry with a fresh rid
    for i in range(4):
        rid = f"cm{i}"
        plan = FaultPlan(seed=7 + i, rules=[
            FaultRule(kind="duplicate", tag_prefix="pg:", prob=0.5),
            FaultRule(kind="corrupt", tag_prefix="pg:", after=1,
                      max_count=1),
            FaultRule(kind="drop", tag_prefix="pg:", after=2,
                      max_count=1),
            FaultRule(kind="reorder", tag_prefix="pg:", prob=0.4),
            FaultRule(kind="drop", tag_prefix="rs:", max_count=1)])
        src_w = mig_pair.MigrationWorker(
            mig_pair.src_e,
            FaultyTransport(LoopbackTransport(f"cmsrc{i}", mig_pair.net),
                            plan),
            ack_timeout=0.25, retries=10)
        # the source must serve its own transport: after the handoff
        # the client stream is fed by the target's tok:/fin: relay
        th = threading.Thread(target=src_w.serve_forever, daemon=True)
        th.start()
        req = mig_pair.src_e.submit(MIG_PROMPT, MIG_MAX_NEW,
                                    request_id=rid)
        deadline = time.monotonic() + 30
        while len(req.tokens) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        moved = src_w.migrate_out(rid, "dst")
        got = [int(t) for t in req.wait(60)]
        src_w.stop()
        th.join(timeout=2)
        assert got == mig_pair.ref
        assert req.error is None and req.done.is_set()
        if moved:
            break
    else:
        pytest.fail("handoff never outran the decode in 4 attempts")
    assert plan.events, "no fault fired — the plan never engaged"
    assert src_w.stats["migrated_out"] == 1
    assert mig_pair.dst_w.stats["migrated_in"] >= 1
    # the faulted staging fully drained into the adoption
    deadline = time.monotonic() + 5.0
    while (rid in mig_pair.dst_w.stager._staged
           and time.monotonic() < deadline):
        time.sleep(0.02)
    assert rid not in mig_pair.dst_w.stager._staged
    _mig_no_pool_leaks(mig_pair.src_e, mig_pair.dst_e)


@pytest.mark.slow
def test_chaos_mid_speculation_migration_rs_faults_bit_identical():
    """§22 chaos acceptance: a SPECULATING row (prompt-lookup proposer,
    adaptive K live) hands off mid-decode while seeded faults hammer
    the rs: resume-state frame — the frame that now carries the §22
    spec_k/spec_ewma scalars.  Drops stall into ack-timeout retries,
    corrupt frames are detected and retransmitted; the handoff still
    completes (or legally resolves locally), the stream is
    bit-identical to the never-migrated spec run, staging drains to
    zero bytes, and no pool page leaks on either replica."""
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)
    from distributed_inference_demo_tpu.runtime.migration import (
        MigrationWorker)
    cfg = get_model_config(MODEL)
    params = init_full_params(jax.random.PRNGKey(0), cfg)

    def mk():
        return ContinuousBatchingEngine(
            cfg, params, max_seq=512, max_batch=2, sampling=GREEDY,
            kv_cache_blocks=80, kv_block_tokens=8,
            prompt_lookup=True, num_draft=3)

    net = LoopbackNetwork()
    src_e, dst_e = mk(), mk()
    dst_w = MigrationWorker(dst_e, LoopbackTransport("smdst", net),
                            ack_timeout=10.0)
    th = threading.Thread(target=dst_w.serve_forever, daemon=True)
    th.start()
    try:
        ref = [int(t) for t in src_e.submit(MIG_PROMPT,
                                            MIG_MAX_NEW).wait(180)]
        # a spec row emits several tokens per round, so the faulted
        # handoff races a faster decode than the plain chaos test —
        # same retry idiom, fresh rid + seed per attempt
        moved = False
        for i in range(4):
            rid = f"sm{i}"
            plan = FaultPlan(seed=7 + i, rules=[
                FaultRule(kind="drop", tag_prefix="rs:", max_count=1),
                FaultRule(kind="corrupt", tag_prefix="rs:", after=1,
                          max_count=1),
                FaultRule(kind="duplicate", tag_prefix="rs:", prob=0.5),
                FaultRule(kind="duplicate", tag_prefix="pg:", prob=0.3),
                FaultRule(kind="reorder", tag_prefix="pg:", prob=0.3)])
            # tight ack timeout: each fault still costs a real
            # stall-and-retry, but the handoff can beat a spec row
            # that emits K+1 tokens per dispatch
            src_w = MigrationWorker(
                src_e,
                FaultyTransport(LoopbackTransport(f"smsrc{i}", net),
                                plan),
                ack_timeout=0.05, retries=10)
            sth = threading.Thread(target=src_w.serve_forever,
                                   daemon=True)
            sth.start()
            req = src_e.submit(MIG_PROMPT, MIG_MAX_NEW, request_id=rid)
            deadline = time.monotonic() + 30
            while len(req.tokens) < 2 and time.monotonic() < deadline:
                time.sleep(0.002)
            moved = src_w.migrate_out(rid, "smdst")
            got = [int(t) for t in req.wait(120)]
            src_w.stop()
            sth.join(timeout=2)
            assert got == ref
            assert req.error is None and req.done.is_set()
            if moved:
                break
        else:
            pytest.fail("spec handoff never outran the decode in 4 "
                        "attempts")
        assert plan.events, "no fault fired — the plan never engaged"
        assert src_w.stats["migrated_out"] == 1
        assert dst_w.stats["migrated_in"] >= 1
        deadline = time.monotonic() + 5.0
        while (rid in dst_w.stager._staged
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert rid not in dst_w.stager._staged
        assert dst_w.staged_bytes == 0
        _mig_no_pool_leaks(src_e, dst_e)
    finally:
        dst_w.stop()
        th.join(timeout=2)
        src_e.close()
        dst_e.close()


def test_chaos_source_crash_mid_migration_promotes_or_survives(
        mig_pair):
    """crash_after on the source transport mid-protocol.  Wherever the
    crash lands, no token is ever lost: before the phase-1 manifest the
    never-frozen row completes locally; after it the target holds a
    complete staged checkpoint and ``promote_staged`` resumes it — the
    promoted stream (snapshot prefix + re-decoded tail) is bit-identical
    to the reference, and staging held ZERO pool pages throughout."""
    promoted = None
    for i in range(3):
        rid = f"cp{i}"
        plan = FaultPlan(seed=31 + i, rules=[
            FaultRule(kind="crash_after", n_msgs=2 + i)])
        src_w = mig_pair.MigrationWorker(
            mig_pair.src_e,
            FaultyTransport(LoopbackTransport(f"cpsrc{i}", mig_pair.net),
                            plan),
            ack_timeout=0.5, retries=1)
        req = mig_pair.src_e.submit(MIG_PROMPT, MIG_MAX_NEW,
                                    request_id=rid)
        deadline = time.monotonic() + 30
        while len(req.tokens) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        with pytest.raises(InjectedCrash):
            src_w.migrate_out(rid, "dst")
        # give the already-delivered frames a beat to process, then try
        # to promote the orphaned staging on the target
        deadline = time.monotonic() + 3.0
        while promoted is None and time.monotonic() < deadline:
            promoted = mig_pair.dst_w.promote_staged(rid)
            if promoted is None:
                time.sleep(0.05)
        if promoted is not None:
            break
        # crash landed before the phase-1 manifest: staging is partial
        # (zero pool pages by construction) — the source row, never
        # frozen, just keeps decoding to the bit-identical stream
        assert [int(t) for t in req.wait(60)] == mig_pair.ref
        assert req.error is None
        mig_pair.dst_w.handle_message(f"pgx:{rid}", b"")
        assert rid not in mig_pair.dst_w.stager._staged
        assert mig_pair.dst_w.staged_bytes == 0
    else:
        pytest.fail("no crash point left a promotable checkpoint")
    assert [int(t) for t in promoted.wait(60)] == mig_pair.ref
    assert mig_pair.dst_w.stats["promoted_requests"] >= 1
    _mig_no_pool_leaks(mig_pair.dst_e)
