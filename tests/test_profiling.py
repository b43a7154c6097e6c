"""The cost observatory (docs/DESIGN.md §20): dispatch signatures are
stable, sampling keeps the off-path free (zero syncs, zero clock reads
on unsampled dispatches), the compile ledger feeds recompile_storm
through the documented decision table, HBM watermarks are monotone and
retire on engine close, and workload sketches are byte-deterministic
artifacts the planner parses as workload input."""

import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

from distributed_inference_demo_tpu.planner import (
    SketchError, load_workload_sketch, plan_from_sketch)
from distributed_inference_demo_tpu.telemetry import profiling
from distributed_inference_demo_tpu.telemetry.anomaly import (
    AnomalyDetector, AnomalyMonitor, Thresholds)
from distributed_inference_demo_tpu.telemetry.profiling import (
    CompileTracker, DispatchProfiler, HbmWatermarks,
    WorkloadSketchRecorder, batch_bucket, dispatch_signature,
    kv_dispatch_bytes, merge_sketches, parse_signature, render_sketch)

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


class FakeClock:
    """Deterministic clock: every call returns the current time, and the
    call COUNT is the syncs-proxy the overhead contract pins."""

    def __init__(self, t: float = 1000.0, step: float = 0.0):
        self.t = t
        self.step = step
        self.calls = 0

    def __call__(self) -> float:
        self.calls += 1
        self.t += self.step
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# -- dispatch signatures ----------------------------------------------------

def test_signature_stability_and_bucketing():
    """Identical call shapes map to identical signatures; near-identical
    batch sizes share a pow2 bucket (slots vary by ±1 constantly — the
    cost regime doesn't fork per unit of batch)."""
    a = dispatch_signature("mixed_step", batch=5, chunk=4, kv_dtype="int8")
    b = dispatch_signature("mixed_step", batch=5, chunk=4, kv_dtype="int8")
    assert a == b == "mixed_step|b8|c4|int8"
    for n in (5, 6, 7, 8):
        assert batch_bucket(n) == 8
    assert batch_bucket(9) == 16
    assert batch_bucket(0) == 1          # empty active set still keys
    assert dispatch_signature("prefill") == "prefill|b1|c0|bf16"


def test_signature_parse_roundtrip():
    sig = dispatch_signature("paged_multi_step", batch=12, chunk=8,
                             kv_dtype="int4")
    assert parse_signature(sig) == {"program": "paged_multi_step",
                                    "batch_bucket": 16, "chunk": 8,
                                    "kv_dtype": "int4"}
    with pytest.raises(ValueError):
        parse_signature("not-a-signature")


# -- sampled dispatch profiler ----------------------------------------------

def test_sampling_cadence_every_nth_per_signature():
    clock = FakeClock(step=0.001)
    prof = DispatchProfiler(sample_n=4, clock=clock)
    sampled = [prof.begin("p|b1|c0|bf16") is not None for _ in range(12)]
    assert sampled == [False, False, False, True] * 3
    # cadence is PER signature: a second signature has its own counter
    assert prof.begin("q|b1|c0|bf16") is None
    assert prof.dispatch_counts() == {"p|b1|c0|bf16": 12,
                                      "q|b1|c0|bf16": 1}


def test_unsampled_path_is_free_no_clock_no_stats():
    """The §20 overhead contract: an UNSAMPLED begin/end pair touches
    the clock zero times (the clock read is the proxy for the
    block_until_ready sync end() would otherwise pay) and allocates no
    per-signature stats."""
    clock = FakeClock(step=0.001)
    prof = DispatchProfiler(sample_n=64, clock=clock)
    for _ in range(63):
        t0 = prof.begin("p|b1|c0|bf16")
        assert t0 is None
        assert prof.end("p|b1|c0|bf16", t0, out=object(),
                        hbm_bytes=10 ** 9) is None
    assert clock.calls == 0
    assert prof.snapshot() == {}
    # the 64th dispatch is the sampled one: exactly two clock reads
    t0 = prof.begin("p|b1|c0|bf16")
    assert t0 is not None
    assert prof.end("p|b1|c0|bf16", t0) is not None
    assert clock.calls == 2


def test_sample_n_zero_disables_even_counting():
    """DWT_PROFILE_SAMPLE_N=0: begin returns None without touching ANY
    state — the observatory is bit-for-bit absent from the hot path."""
    prof = DispatchProfiler(sample_n=0)
    for _ in range(5):
        assert prof.begin("p|b1|c0|bf16") is None
    assert prof.dispatch_counts() == {}
    assert prof.snapshot() == {}


def test_profiler_snapshot_percentiles_and_attribution(monkeypatch):
    """Sampled durations roll up to deterministic p50/p95/mean, and an
    hbm_bytes attribution yields achieved GB/s reconciled against the
    published peak of the device kind (a 100 GB/s kind injected into
    the table here: the CPU the tests run on has no entry)."""
    import jax
    monkeypatch.setitem(
        profiling.DEVICE_PEAKS, jax.devices()[0].device_kind,
        profiling.DevicePeaks(100.0, 1.0, 1.0, "test"))
    monkeypatch.setattr(profiling, "_ROOFLINE_CACHE", [])
    clock = FakeClock()
    prof = DispatchProfiler(sample_n=1, clock=clock)
    sig = dispatch_signature("decode_loop", batch=8, chunk=4)
    for ms in (1.0, 2.0, 3.0, 4.0, 5.0):
        t0 = prof.begin(sig)
        clock.advance(ms / 1e3)
        # 15 MB in `ms` — achieved GB/s varies per sample
        prof.end(sig, t0, hbm_bytes=15 * 1000 * 1000)
    snap = prof.snapshot()[sig]
    assert snap["dispatches"] == snap["samples"] == 5
    assert snap["p50_ms"] == 3.0          # nearest-rank over 5 samples
    assert snap["p95_ms"] == 5.0
    assert snap["mean_ms"] == 3.0
    # 75 MB over 15 ms total = 5 GB/s; ceiling 100 GB/s -> 0.05
    assert snap["achieved_gbs"] == 5.0
    assert snap["roofline_frac"] == 0.05


def test_kv_dispatch_bytes_tracks_quant_math():
    """The attribution uses the one-owner byte math in ops/quant.py:
    int8 pages are narrower than bf16 (scale sidecar accounted), K and
    V both counted."""
    bf16 = kv_dispatch_bytes(16, 4, 2, 64, "bf16", "bfloat16")
    int8 = kv_dispatch_bytes(16, 4, 2, 64, "int8", "bfloat16")
    assert bf16 == 16 * 4 * 2 * 2 * (64 * 2)
    assert 0 < int8 < bf16
    assert kv_dispatch_bytes(0, 4, 2, 64, None, "bfloat16") == 0


# -- compile observability --------------------------------------------------

class FakeJit:
    """A jit-shaped callable: _cache_size grows on unseen static args."""

    def __init__(self):
        self.cache = set()

    def _cache_size(self):
        return len(self.cache)

    def __call__(self, static_arg):
        self.cache.add(static_arg)
        return static_arg


def test_compile_tracker_counts_cache_growth():
    tracker = CompileTracker()
    fn = tracker.wrap("mixed_step", FakeJit(), variant_budget=2)
    fn("v1")
    fn("v1")                               # cache hit: not a compile
    fn("v2")
    snap = tracker.snapshot()["mixed_step"]
    assert snap["compiles"] == 2
    assert snap["cache_entries"] == 2
    assert snap["variant_budget"] == 2
    assert snap["compile_seconds"] >= 0.0
    # an unbudgeted program records None (ineligible for recompile_storm)
    tracker.wrap("prefill", FakeJit())("v1")
    assert tracker.snapshot()["prefill"]["variant_budget"] is None


def test_compile_tracker_passthrough_without_cache_size():
    """Wrapping a plain callable (no _cache_size) must pass through
    untouched — no accounting, no crash."""
    tracker = CompileTracker()
    fn = tracker.wrap("plain", lambda x: x + 1)
    assert fn(41) == 42
    assert tracker.snapshot()["plain"]["compiles"] == 0


def _storm_thresholds(slack=0, sustain=1):
    return Thresholds(recompile_slack=slack, sustain=sustain,
                      cooldown_s=300.0)


def test_recompile_storm_decision_table():
    """The detector's full decision table under an injected clock:
    within-budget quiet, budget+slack tolerated, overrun fires (once,
    cooldown eats repeats), slack=-1 disables, unbudgeted ignored."""
    clock = FakeClock()

    def observe(det, compiles, budget, slack_prog="mixed_step"):
        out = det.observe({"compile": {slack_prog: {
            "compiles": compiles, "variant_budget": budget,
            "compile_seconds": 1.5, "cache_entries": compiles}}})
        clock.advance(1.0)
        return out

    # within budget: never fires
    det = AnomalyDetector(_storm_thresholds(), clock=clock)
    for _ in range(3):
        assert observe(det, 2, 2) == []
    # overrun: fires exactly once (cooldown), critical, named detail
    fired = []
    for _ in range(5):
        fired += observe(det, 3, 2)
    assert [a.kind for a in fired] == ["recompile_storm"]
    assert fired[0].severity == "critical"
    assert fired[0].detail == {"program": "mixed_step", "compiles": 3,
                               "variant_budget": 2, "slack": 0,
                               "compile_seconds": 1.5}
    # slack tolerates exactly that many extra compiles
    det = AnomalyDetector(_storm_thresholds(slack=1), clock=clock)
    assert observe(det, 3, 2) == []
    assert [a.kind for a in observe(det, 4, 2)] == ["recompile_storm"]
    # slack=-1 disables the detector outright
    det = AnomalyDetector(_storm_thresholds(slack=-1), clock=clock)
    for _ in range(3):
        assert observe(det, 10, 2) == []
    # unbudgeted programs (variant_budget None) never fire
    det = AnomalyDetector(_storm_thresholds(), clock=clock)
    for _ in range(3):
        assert observe(det, 50, None) == []


def test_recompile_storm_sustain_and_recovery():
    """sustain=3: two breaches + a recovered observation + two more
    breaches must NOT fire (consecutive means consecutive)."""
    clock = FakeClock()
    det = AnomalyDetector(_storm_thresholds(sustain=3), clock=clock)

    def obs(compiles):
        out = det.observe({"compile": {"mixed_step": {
            "compiles": compiles, "variant_budget": 2}}})
        clock.advance(1.0)
        return out

    assert obs(3) == [] and obs(3) == []
    assert obs(2) == []                    # recovery clears the streak
    assert obs(3) == [] and obs(3) == []
    assert [a.kind for a in obs(3)] == ["recompile_storm"]


def test_recompile_storm_end_to_end_with_real_jit(tmp_path):
    """The acceptance scenario: a REAL jitted program wrapped as
    mixed_step with the §19 two-variant budget compiles a third variant
    — the observatory's compile fragment turns it into a critical
    recompile_storm with a postmortem bundle on disk."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.telemetry import postmortem

    tracker = CompileTracker()
    step = tracker.wrap("mixed_step", jax.jit(lambda x: x * 2),
                        variant_budget=2)
    for n in (2, 4, 8):                   # three shapes = three variants
        np.asarray(step(jnp.ones((n,), jnp.float32)))
    snap = tracker.snapshot()["mixed_step"]
    assert snap["compiles"] == 3
    assert snap["cache_entries"] == 3
    assert snap["compile_seconds"] > 0

    clock = FakeClock()
    writer = postmortem.PostmortemWriter(str(tmp_path), clock=clock)
    postmortem.set_postmortem_writer(writer)
    try:
        mon = AnomalyMonitor(
            AnomalyDetector(_storm_thresholds(), clock=clock),
            min_interval_s=0.0, clock=clock)
        fired = mon.observe({"compile": tracker.snapshot()})
        assert [a.kind for a in fired] == ["recompile_storm"]
        assert fired[0].detail["program"] == "mixed_step"
        assert len(mon.bundles) == 1
        bundle = Path(mon.bundles[0])
        assert bundle.is_dir()
        manifest = json.loads((bundle / "manifest.json").read_text())
        assert manifest["reason"] == "recompile_storm"
    finally:
        postmortem.set_postmortem_writer(None)


def test_every_dispatch_kind_compiles_the_budget_and_no_storm():
    """``mixed_step`` is keyed by the segments a dispatch packed (none:
    PR 33; one .. the budget's: PR 39).  A run with every kind of
    dispatch (chunks alone, a final, decode alone; two segments, one,
    none) compiles exactly the ``variant_budget`` the engine declares,
    the budget's two segments + 1, all of it before the first request,
    so the storm detector has nothing to say at slack 0."""
    import jax

    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    profiling.reset_observatory()
    try:
        cfg = get_model_config("llama-test")
        params = init_full_params(jax.random.PRNGKey(0), cfg)
        with ContinuousBatchingEngine(
                cfg, params, max_seq=96, max_batch=2,
                sampling=SamplingParams(greedy=True), kv_block_tokens=8,
                prefill_chunk=8, decode_block=4,
                mixed_token_budget=16) as eng:
            # 37 tokens over two segments a dispatch: 16 + 16 + (5, final)
            eng.submit(list(range(1, 38)), 10).wait(timeout=300)
            eng.submit([3, 14, 15], 6).wait(timeout=300)
            stats = eng.stats()
            recent = eng.anomaly.state()["recent"]
        dt = stats["dispatch_trace"]
        recs = [dict(zip(dt["fields"], r)) for r in dt["recent"]]
        kinds = {(r["segments"] > 0, r["finals"] > 0) for r in recs}
        assert kinds == {(True, False), (True, True), (False, False)}
        comp = stats["compile"]["mixed_step"]
        assert {r["segments"] for r in recs} == {0, 1, 2}
        assert comp["compiles"] == comp["cache_entries"] \
            == comp["variant_budget"] == 3
        clock = FakeClock()
        det = AnomalyDetector(_storm_thresholds(), clock=clock)
        assert det.observe({"compile": stats["compile"]}) == []
        assert not [a for a in recent if a["kind"] == "recompile_storm"]
    finally:
        profiling.reset_observatory()


# -- HBM watermark ledger ---------------------------------------------------

def test_hbm_watermark_monotone_until_reset():
    hbm = HbmWatermarks()
    hbm.sample("kv_page_pool", 100)
    hbm.sample("kv_page_pool", 400)
    hbm.sample("kv_page_pool", 50)        # pool shrank; watermark holds
    w = hbm.watermarks()["kv_page_pool"]
    assert w == {"bytes": 50, "watermark_bytes": 400}
    hbm.sample("stage_pool", 7)
    hbm.reset("kv_page_pool")             # one owner retires
    assert "kv_page_pool" not in hbm.watermarks()
    assert hbm.watermarks()["stage_pool"]["watermark_bytes"] == 7
    hbm.reset()
    assert hbm.watermarks() == {}


def test_engine_feeds_watermarks_and_sketch_reset_on_close():
    """End to end on the paged scheduler: serving one request feeds the
    kv_page_pool watermark and the workload sketch; close() retires the
    engine's watermark owners (reset-on-close) while the process-wide
    sketch survives."""
    import jax

    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)
    from distributed_inference_demo_tpu.ops.sampling import SamplingParams
    from distributed_inference_demo_tpu.runtime.batching import (
        ContinuousBatchingEngine)

    profiling.reset_observatory()
    try:
        cfg = get_model_config("llama-test")
        params = init_full_params(jax.random.PRNGKey(0), cfg)
        with ContinuousBatchingEngine(
                cfg, params, max_seq=64, max_batch=2,
                sampling=SamplingParams(greedy=True),
                prompt_buckets=(16,)) as eng:
            eng.submit([3, 14, 15, 92], 6).wait(timeout=300)
            hbm = profiling.get_hbm_watermarks().watermarks()
            assert hbm["kv_page_pool"]["watermark_bytes"] > 0
            # the scheduler's dispatches are being counted (default
            # sampling keeps the exact-count half of the observatory on)
            assert profiling.get_profiler().dispatch_counts()
            # compile ledger saw the paged programs compile, and the
            # budgeted ones carry the documented invariant
            comp = profiling.get_compile_tracker().snapshot()
            assert any(e["compiles"] > 0 for e in comp.values())
        assert "kv_page_pool" not in (
            profiling.get_hbm_watermarks().watermarks())
        sk = profiling.get_sketch()
        assert sk.requests == 1
        assert sk.decode_tokens.count >= 1
    finally:
        profiling.reset_observatory()


# -- workload sketches ------------------------------------------------------

def _record_trace(rec: WorkloadSketchRecorder) -> None:
    t = 100.0
    for i, (plen, tenant) in enumerate([(40, "a"), (600, "b"), (40, "a"),
                                        (3000, "a")]):
        rec.record_request(plen, tenant=tenant, now=t + i * 0.5)
    rec.record_prefix(32, 40)
    rec.record_prefix(0, 600)
    for n in (10, 20, 200):
        rec.record_decode(n)


def test_sketch_byte_determinism():
    """Identical traces fold to byte-identical canonical JSON — the
    contract GET /sketch serves verbatim and tools/sketch.py preserves."""
    a, b = WorkloadSketchRecorder(), WorkloadSketchRecorder()
    _record_trace(a)
    _record_trace(b)
    assert a.to_json() == b.to_json()
    obj = json.loads(a.to_json())
    assert obj["schema_version"] == profiling.SKETCH_SCHEMA_VERSION
    assert obj["requests"] == 4
    assert obj["window_s"] == 1.5
    assert obj["tenants"] == {"a": 3, "b": 1}
    assert obj["prefix_hit"] == {"matched_tokens": 32,
                                 "prompt_tokens": 640,
                                 "share": 0.05}
    # canonical form survives a parse/render round trip byte-for-byte
    assert render_sketch(obj) == a.to_json()


def test_sketch_merge_deterministic_and_schema_gated():
    """The gateway's fleet merge: section order doesn't matter, counts
    sum bin-wise, window is the max, and a schema-mismatched replica is
    dropped (named) instead of poisoning the merge."""
    a, b = WorkloadSketchRecorder(), WorkloadSketchRecorder()
    _record_trace(a)
    b.record_request(64, tenant="c", now=5.0)
    b.record_request(64, tenant="c", now=9.0)
    sa, sb = a.snapshot(), b.snapshot()
    stale = dict(sb, schema_version=999)
    merged = merge_sketches([("r1", sb), ("r0", sa), ("r2", stale)])
    flipped = merge_sketches([("r2", stale), ("r0", sa), ("r1", sb)])
    assert render_sketch(merged) == render_sketch(flipped)
    assert merged["replicas"] == ["r0", "r1"]
    assert merged["dropped_replicas"] == ["r2"]
    assert merged["requests"] == 6
    assert merged["tenants"] == {"a": 3, "b": 1, "c": 2}
    assert merged["window_s"] == 4.0      # max over sections, r2 included
    assert (merged["prompt_tokens"]["count"]
            == sa["prompt_tokens"]["count"] + sb["prompt_tokens"]["count"])


def test_gateway_fleet_sketch_socket_free():
    """The gateway's federated GET /sketch through the injectable
    fetcher: up replicas merge (sorted by rid), an unreachable replica
    is skipped — never a crash, never a poisoned merge."""
    from distributed_inference_demo_tpu.runtime.gateway.server import (
        GatewayHTTPServer)

    class Reg:
        def up_replicas(self):
            return ["h:2", "h:1", "h:3"]

        def endpoint(self, rid):
            host, port = rid.rsplit(":", 1)
            return host, int(port)

    a, b = WorkloadSketchRecorder(), WorkloadSketchRecorder()
    _record_trace(a)
    b.record_request(64, tenant="c", now=1.0)
    payloads = {"h:1": a.snapshot(), "h:2": b.snapshot()}

    def fetch(rid, host, port):
        if rid not in payloads:
            raise ConnectionError("replica down")
        return payloads[rid]

    gw = GatewayHTTPServer(Reg(), None, sketch_fetcher=fetch)
    merged = gw._fleet_sketch()
    assert merged["replicas"] == ["h:1", "h:2"]
    assert merged["requests"] == 5
    assert merged["tenants"] == {"a": 3, "b": 1, "c": 1}
    assert "h:3" not in merged.get("dropped_replicas", [])


def test_sketch_feeds_planner_as_workload_input():
    """The loop closes: a recorder artifact parses into the planner's
    WorkloadSketch and drives plan_from_sketch to a real plan whose ctx
    came from the measured p95s discounted by the prefix share."""
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.planner import DeviceProfile

    rec = WorkloadSketchRecorder()
    _record_trace(rec)
    ws = load_workload_sketch(rec.to_json())
    assert ws.requests == 4
    assert ws.window_s == 1.5
    assert ws.arrival_rate == pytest.approx(4 / 1.5)
    assert ws.prompt_p50 == 64.0          # bucket upper edges
    assert ws.prompt_p95 == 4096.0
    assert ws.decode_p50 == 32.0
    assert ws.prefix_share == 0.05
    assert ws.ctx_tokens == 4096 + 256

    cfg = get_model_config("llama-test")
    devices = [DeviceProfile(device_id=f"d{i}",
                             address=f"10.0.0.{i}:9000",
                             flops_per_sec=1e12, memory_bytes=16 << 30,
                             platform="cpu", chips=1,
                             egress_bandwidth=1e9, egress_latency=1e-3)
               for i in range(2)]
    plan = plan_from_sketch(cfg, "llama-test", devices, rec.to_json())
    assert sum(b - a for a, b in plan.stage_ranges.values()) \
        == cfg.num_layers


def test_sketch_loader_rejects_drift():
    rec = WorkloadSketchRecorder()
    rec.record_request(10)
    obj = rec.snapshot()
    with pytest.raises(SketchError):
        load_workload_sketch(dict(obj, schema_version=999))
    missing = dict(obj)
    del missing["interarrival_s"]
    with pytest.raises(SketchError):
        load_workload_sketch(missing)
    with pytest.raises(SketchError):
        load_workload_sketch([1, 2, 3])


def test_check_sketch_schema_lint_is_clean():
    """The tier-1 half of tools/check_sketch_schema.py: the recorder's
    and the planner's pinned schema copies agree RIGHT NOW."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_sketch_schema
        assert check_sketch_schema.check() == []
    finally:
        sys.path.remove(str(REPO / "tools"))


def test_observatory_state_shape(monkeypatch):
    """/debugz section: every ledger present, sample_n from the env."""
    monkeypatch.setenv("DWT_PROFILE_SAMPLE_N", "16")
    profiling.reset_observatory()
    try:
        state = profiling.observatory_state()
        assert state["sample_n"] == 16
        for key in ("profile", "compile", "hbm"):
            assert state[key] == {}
        assert state["sketch_requests"] == 0
    finally:
        monkeypatch.delenv("DWT_PROFILE_SAMPLE_N", raising=False)
        profiling.reset_observatory()


def test_peaks_table_refuses_unknown_device_kind(monkeypatch, caplog):
    """One peaks table keyed by device_kind: the v5e row carries the
    published numbers and its source; a kind the table lacks is an
    error for callers that need a peak, and the profiler's roofline
    ratio is simply absent for it (said once), never computed against
    a default."""
    v5e = profiling.device_peaks("TPU v5 lite")
    assert (v5e.hbm_gbs, v5e.bf16_tflops, v5e.hbm_gb) == (819.0, 197.0, 16.0)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no published peaks.*'cpu'"):
        profiling.device_peaks("cpu")

    monkeypatch.setattr(profiling, "_ROOFLINE_CACHE", [])
    clock = FakeClock()
    prof = DispatchProfiler(sample_n=1, clock=clock)
    sig = dispatch_signature("decode_loop", batch=8, chunk=4)
    with caplog.at_level("WARNING", logger=profiling.__name__):
        for _ in range(3):
            t0 = prof.begin(sig)
            clock.advance(1e-3)
            prof.end(sig, t0, hbm_bytes=10 ** 6)
        snap = prof.snapshot()[sig]
    assert "achieved_gbs" in snap and "roofline_frac" not in snap
    said = [r for r in caplog.records if "roofline_ratio" in r.getMessage()]
    assert len(said) == 1
