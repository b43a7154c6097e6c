"""Elasticity: live migration, scale-up/down, failure-triggered re-planning
with token-preserving resume, and the control-plane heartbeat wiring.

The property under test everywhere: whatever happens to the pipeline
topology mid-run, greedy output must equal the single-engine reference
token for token (the reference can only hang on failure — SURVEY.md §5.3).
"""

import threading
import time

import numpy as np
import pytest

import jax

from distributed_inference_demo_tpu.comm.transport import (
    LoopbackNetwork, LoopbackTransport, TransportTimeout)
from distributed_inference_demo_tpu.control.pool import (
    DeviceInfo, DevicePoolManager, DeviceRole)
from distributed_inference_demo_tpu.models import get_model_config
from distributed_inference_demo_tpu.models.decoder import init_full_params
from distributed_inference_demo_tpu.ops.sampling import SamplingParams
from distributed_inference_demo_tpu.runtime import InferenceEngine
from distributed_inference_demo_tpu.runtime.elastic import (
    ElasticHeader, ElasticStageRuntime, ElasticWorker)

GREEDY = SamplingParams(greedy=True)
PROMPT = np.array([[5, 17, 42, 7, 99, 3, 12, 56]], dtype=np.int32)
MODEL = "llama-test"


def reference_tokens(prompt, max_new):
    cfg = get_model_config(MODEL)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(cfg, params, max_seq=64,
                           sampling=GREEDY).generate(prompt, max_new).tokens


class DyingWorker(ElasticWorker):
    """Simulates a crash: stops serving after N data chunks (no goodbye)."""

    def __init__(self, *args, die_after: int, **kw):
        super().__init__(*args, **kw)
        self.die_after = die_after
        self._seen = 0

    def serve_forever(self, idle_timeout=None):
        while True:
            try:
                tag, payload = self.transport.recv_any(
                    timeout=idle_timeout or self.step_timeout)
            except TransportTimeout:
                return          # clean idle exit (mirrors the base class)
            if tag.startswith("h:"):
                self._seen += 1
                if self._seen > self.die_after:
                    return      # crash: message dropped on the floor
            if not self.handle_message(tag, payload):
                return


def build_elastic(num_stages, dying=None, spares=0, max_seq=64):
    """Elastic pipeline on loopback; returns (header, workers, threads).

    ``dying``: {device_id: die_after} — those workers crash after N chunks.
    """
    cfg = get_model_config(MODEL)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    from distributed_inference_demo_tpu.models.base import split_layer_ranges
    specs = split_layer_ranges(cfg.num_layers, num_stages)
    net = LoopbackNetwork()
    n_all = num_stages + spares
    ids = [f"s{i}" for i in range(n_all)]
    transports = [LoopbackTransport(d, net) for d in ids]

    header = ElasticHeader(
        ElasticStageRuntime(cfg, specs[0], full, max_seq, GREEDY),
        transports[0], chain=ids[:num_stages], step_timeout=60,
        poll_interval=0.2)
    workers = []
    dying = dying or {}
    for i in range(1, n_all):
        # spares start parked on the last stage's range; a reshard
        # reassigns them before they ever see traffic.
        spec = specs[min(i, num_stages - 1)]
        rt = ElasticStageRuntime(cfg, spec, full, max_seq, GREEDY)
        if ids[i] in dying:
            workers.append(DyingWorker(
                rt, transports[i],
                next_id=ids[i + 1] if i + 1 < num_stages else None,
                header_id=ids[0], step_timeout=60,
                die_after=dying[ids[i]]))
        else:
            workers.append(ElasticWorker(
                rt, transports[i],
                next_id=ids[i + 1] if i + 1 < num_stages else None,
                header_id=ids[0], step_timeout=60))
    threads = [threading.Thread(target=w.serve_forever, args=(30,),
                                daemon=True) for w in workers]
    for t in threads:
        t.start()
    return header, workers, threads


def _stop_all(header, extra_ids=()):
    header.shutdown_pipeline()
    for dev in extra_ids:
        header.transport.send(dev, "stop", b"")


@pytest.mark.slow
def test_live_migration_scale_down_park_and_rejoin():
    """Planned migration: 3 stages -> 2 (the dropped live worker is parked:
    caches freed, standing by) -> back to 3 (the spare rejoins).  Every
    configuration must match the reference (the ModifySession capability,
    with a working trigger)."""
    want = reference_tokens(PROMPT, 10)
    header, workers, threads = build_elastic(3)
    got3 = header.generate(PROMPT, 10)
    np.testing.assert_array_equal(got3, want)

    header.reshard(["s0", "s1"])          # drop s2, re-split layers
    assert workers[1].rt._tables == {}     # s2 parked: caches freed
    got2 = header.generate(PROMPT, 10)
    np.testing.assert_array_equal(got2, want)
    assert workers[1].rt._tables == {}     # parked spare saw no traffic

    header.reshard(["s0", "s1", "s2"])    # the parked spare rejoins
    np.testing.assert_array_equal(header.generate(PROMPT, 10), want)
    _stop_all(header)
    for t in threads:
        t.join(timeout=30)


# tier-1 budget: heartbeat-reshard plus the test_migration live tests
# are the quick-lane reps; the scale-up soak rides the slow lane
@pytest.mark.slow
def test_live_migration_scale_up():
    """Scale-up: a spare worker joins the chain via reshard."""
    want = reference_tokens(PROMPT, 10)
    header, workers, threads = build_elastic(2, spares=1)
    np.testing.assert_array_equal(header.generate(PROMPT, 10), want)

    header.reshard(["s0", "s1", "s2"])    # spare s2 becomes the tail
    np.testing.assert_array_equal(header.generate(PROMPT, 10), want)
    assert workers[-1].rt.spec.is_last    # s2 really owns the tail now
    _stop_all(header)
    for t in threads:
        t.join(timeout=30)


@pytest.mark.slow
def test_failure_mid_generation_resumes():
    """A mid-chain worker dies after 4 chunks; a failure signal triggers
    re-planning and the request resumes, producing the exact reference
    tokens (the hang the reference exhibits is the bug, SURVEY.md §5.3)."""
    want = reference_tokens(PROMPT, 12)
    header, workers, threads = build_elastic(3, dying={"s1": 4})

    # watchdog stands in for the heartbeat sweeper (tested separately below)
    killer = threading.Timer(2.0, lambda: header.signal_failure("s1"))
    killer.start()
    got = header.generate(PROMPT, 12)
    np.testing.assert_array_equal(got, want)
    assert header.chain == ["s0", "s2"]
    _stop_all(header)
    killer.cancel()


def test_heartbeat_failure_triggers_reshard():
    """Control-plane wiring: DevicePoolManager's sweeper detects the dead
    device (no heartbeats) and its on_failure callback drives the header's
    reshard — no manual signal anywhere."""
    want = reference_tokens(PROMPT, 12)
    header, workers, threads = build_elastic(3, dying={"s1": 4})

    pool = DevicePoolManager(heartbeat_timeout=1.2)
    for dev in ["s0", "s1", "s2"]:
        pool.register_device(DeviceInfo(device_id=dev, address=dev,
                                        role=DeviceRole.WORKER))
    pool.on_failure(lambda info: header.signal_failure(info.device_id))

    alive = {"s0", "s2"}
    stop_beats = threading.Event()

    def beat():
        while not stop_beats.is_set():
            for dev in alive:
                pool.heartbeat(dev)
            time.sleep(0.2)

    beater = threading.Thread(target=beat, daemon=True)
    beater.start()
    pool.start_sweeper(interval=0.3)
    try:
        got = header.generate(PROMPT, 12)
    finally:
        pool.stop_sweeper()
        stop_beats.set()
    np.testing.assert_array_equal(got, want)
    assert header.chain == ["s0", "s2"]
    assert [d.device_id for d in pool.get_failed_devices()] == ["s1"]
    _stop_all(header)


def test_reshard_below_two_devices_raises():
    header, workers, threads = build_elastic(2)
    with pytest.raises(RuntimeError, match="enough devices"):
        header.reshard(["s0"])
    _stop_all(header)


def test_stale_epoch_ack_does_not_satisfy_reshard():
    """ADVICE r1 #3: a delayed ack from reshard N must not satisfy reshard
    N+1's ack-wait.  No worker threads here — acks are injected by hand."""
    from distributed_inference_demo_tpu.models.base import split_layer_ranges

    cfg = get_model_config(MODEL)
    full = init_full_params(jax.random.PRNGKey(0), cfg)
    specs = split_layer_ranges(cfg.num_layers, 2)
    net = LoopbackNetwork()
    t0 = LoopbackTransport("s0", net)
    t1 = LoopbackTransport("s1", net)
    header = ElasticHeader(
        ElasticStageRuntime(cfg, specs[0], full, 64, GREEDY),
        t0, chain=["s0", "s1"], step_timeout=1.0, poll_interval=0.1)

    # stale ack (epoch 0) already queued when reshard (-> epoch 1) starts:
    # it must be ignored, so the ack-wait times out.
    t1.send("s0", "rack:s1:0", b"")
    with pytest.raises(TransportTimeout, match="reshard acks"):
        header.reshard(["s0", "s1"])

    # a current-epoch ack (next reshard -> epoch 2) satisfies the wait.
    t1.send("s0", "rack:s1:2", b"")
    header.reshard(["s0", "s1"])
    assert header.epoch == 2
