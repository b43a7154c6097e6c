"""Smoke the bench legs' code paths at tiny scale on CPU.

A leg bug found on the real TPU costs chip time, so every leg that can
run its full structure on tiny models must prove it here first.  Numbers
are not asserted — only structure and non-error shape.
"""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import bench  # noqa: E402


@pytest.mark.slow
def test_leg_moe_structure_tiny():
    out = bench._leg_moe(2, 8, 4, moe_model="mixtral-test",
                         dense_model="llama-test")
    assert "error" not in out
    for key in ("moe_bf16", "moe_int8", "dense_equal_active_flops_bf16"):
        assert out[key]["decode_tokens_per_sec"] > 0
        assert out[key]["prefill_tokens_per_sec"] > 0
    assert out["moe_vs_dense_decode"] > 0


def test_bench_engine_latency_percentiles_tiny():
    """The headline legs' TTFT/TPOT block: real
    percentiles, ordered, from the streamed per-request measurement."""
    out = bench._bench_engine("llama-test", 2, 8, 4, latency=True)
    lat = out["latency"]
    assert lat["requests"] >= 1
    for name in ("ttft", "tpot"):
        p50, p95, p99 = (lat[f"{name}_p{q}_ms"] for q in (50, 95, 99))
        assert p50 is not None and p50 > 0
        assert p50 <= p95 <= p99


@pytest.mark.slow
def test_leg_multimodal_structure_tiny():
    out = bench._leg_multimodal(2, 4, scale="tiny",
                                decoder_model="llama-test")
    assert "error" not in out
    enc = out["vision_encoder_llava15_scale"]
    assert enc["images_per_sec"] > 0
    e2e = out["e2e_image_text_generate"]
    assert e2e["decode_tokens_per_sec"] > 0
    assert e2e["image_tokens"] == enc["patches_per_image"]


@pytest.mark.slow
def test_leg_paged_decode_structure_tiny():
    """The paged_decode leg's full structure (dense-escape-hatch
    reference, paged run, admissible table, primed phase) at CPU-viable
    scale — proves the leg before it can burn a TPU session attempt,
    and pins the leg-level acceptance shape: both HBM numbers present,
    a strictly larger admissible batch at every sequence budget, and
    h2d_bytes == 0 on the primed paged path.  The quick lane runs the
    int8 kv-dtype phase only (one extra engine compile); the full
    int8-vs-int4 ordering rides the slow twin below."""
    out = bench._leg_paged_decode("llama-test", 6, slots=2,
                                  prompt_len=16, max_seq=64,
                                  block_tokens=8, n_req=4,
                                  shared_len=8, kv_dtypes=("int8",))
    assert "error" not in out
    assert out["dense"]["tokens_per_sec"] > 0
    assert out["paged"]["tokens_per_sec"] > 0
    assert out["paged_vs_dense_decode"] > 0
    # the HBM story: reserved (dense) vs actually allocated (paged)
    assert out["dense"]["cache_reserved_bytes"] > 0
    assert 0 < out["paged"]["peak_blocks_in_use"] <= out["paged"][
        "pool_blocks"]
    assert (out["paged"]["peak_bytes_in_use"]
            < out["dense"]["cache_reserved_bytes"])
    # the §14 acceptance gate: at the fixed dense byte budget, paged
    # admits a STRICTLY larger batch at every sequence budget
    for seq in ("4096", "8192", "32768"):
        adm = out["admissible"][seq]
        assert adm["paged_max_batch"] > adm["dense_max_batch"]
        assert adm["budget_bytes"] == out["dense"]["cache_reserved_bytes"]
    # primed wave: radix hits reference device pages, zero H2D
    primed = out["paged_primed"]
    assert primed["hit_rate"] == 1.0
    assert primed["reused_tokens"] >= 4 * 8
    assert primed["h2d_bytes"] == 0
    # the §17 kv-dtype gate: at the SAME fixed byte budget, int8 pages
    # (narrower block_bytes, scale sidecar accounted) admit a strictly
    # larger batch than bf16 pages at every sequence budget — and the
    # wave really decoded against the quantized pool
    q = out["kv_dtype"]["int8"]
    assert q["tokens_per_sec"] > 0
    assert 0 < q["peak_blocks_in_use"]
    assert 0 < q["block_bytes"] < out["paged"]["block_bytes"]
    assert q["scale_block_bytes"] > 0
    assert q["pool_capacity_bytes"] > 0
    for seq in ("4096", "8192", "32768"):
        adm8 = q["admissible"][seq]
        assert adm8["budget_bytes"] == out["dense"]["cache_reserved_bytes"]
        assert (adm8["paged_max_batch"]
                > out["admissible"][seq]["paged_max_batch"])


@pytest.mark.slow
def test_leg_paged_decode_kv_dtype_axis_full():
    """Slow twin of the quick dryrun above: the FULL §17 kv-dtype axis
    (int8 AND int4) with the width ordering pinned — int4 blocks are
    narrower than int8, which are narrower than bf16, and the
    admissible batch grows strictly with each narrowing at every
    sequence budget."""
    out = bench._leg_paged_decode("llama-test", 6, slots=2,
                                  prompt_len=16, max_seq=64,
                                  block_tokens=8, n_req=4,
                                  shared_len=8,
                                  kv_dtypes=("int8", "int4"))
    assert "error" not in out
    q8, q4 = out["kv_dtype"]["int8"], out["kv_dtype"]["int4"]
    assert q8["tokens_per_sec"] > 0 and q4["tokens_per_sec"] > 0
    assert q4["block_bytes"] < q8["block_bytes"] < out["paged"][
        "block_bytes"]
    # int4 carries the wider sidecar (scale + zero-point per token-head)
    assert q4["scale_block_bytes"] > q8["scale_block_bytes"] > 0
    for seq in ("4096", "8192", "32768"):
        bf16_b = out["admissible"][seq]["paged_max_batch"]
        assert (q4["admissible"][seq]["paged_max_batch"]
                > q8["admissible"][seq]["paged_max_batch"]
                > bf16_b)


@pytest.mark.slow
def test_leg_sweep_kv_points_structure_tiny():
    """The sweep's §17 weight-dtype x kv-dtype cross: one batching-
    engine point per pair at the largest batch, each reporting real
    decode throughput against its page pool (int4-KV points included —
    the gather path serves them where the kernel refuses)."""
    out = bench._leg_sweep("llama-test", 16, 4, quants=(False,),
                           batches=(2,), kv_dtypes=("bf16", "int8"))
    assert len(out["points"]) == 1
    kv = out["kv_points"]
    assert [(p["kv_dtype"], p["batch"]) for p in kv] == [("bf16", 2),
                                                         ("int8", 2)]
    for p in kv:
        assert "error" not in p, p
        assert p["engine"] == "batching-paged"
        assert p["decode_tokens_per_sec"] > 0
        assert p["pool_capacity_bytes"] > 0
    assert kv[1]["block_bytes"] < kv[0]["block_bytes"]


@pytest.mark.slow
def test_leg_serving_relative_structure_tiny():
    """The serving_relative leg (VERDICT r5 'Next round' #4): the
    CPU-relative serving ratios — speculative speedup, prompt-lookup
    acceptance, batching throughput-per-slot — with the platform stamp
    that keeps a CPU number from masquerading as a TPU one.  Runs the
    micro variant's shape (the prepass path)."""
    out = bench.run_leg("serving_relative",
                        {"model": "llama-test", "batch": 2,
                         "prompt_len": 32, "new_tokens": 8,
                         "flagship": "llama-test"}, micro=True)
    assert "error" not in out
    assert out["platform"] == "cpu"
    assert out["relative_only"] is True
    assert out["micro"] is True
    assert out["plain_tokens_per_sec"] > 0
    assert out["speculative"]["speedup_vs_plain"] > 0
    assert out["speculative"]["acceptance_rate"] is not None
    assert out["prompt_lookup"]["acceptance_rate"] is not None
    assert out["batching"]["throughput_per_slot"] > 0


def test_long_context_sp_points_structure_tiny(monkeypatch):
    """The sequence-parallel long-context micro points (carried sweep
    satellite): both strategies produce a number (or a per-strategy
    error) — structure proven on the CPU mesh at a shrunken context so
    the 32k TPU shape can't burn a session attempt on a structural
    bug."""
    monkeypatch.setenv("BENCH_LONG_CTX_SP", "256")
    points = bench._long_context_sp_points("llama-test", new=4)
    assert [p["strategy"] for p in points] == ["ring", "ulysses"]
    for p in points:
        assert "error" not in p, p
        assert p["sp"] == 2 and p["context"] == 256
        assert p["tokens_per_sec"] > 0


@pytest.mark.slow
def test_leg_fault_recovery_structure_tiny():
    """The fault_recovery leg's full structure (fault-free reference run,
    injected crash_after, reshard + drain/resume timing) on CPU — the
    tier-1 dryrun the ISSUE-5 bench satellite requires."""
    out = bench._leg_fault_recovery("llama-test", new_tokens=10,
                                    crash_after_msgs=6)
    assert "error" not in out
    assert out["tokens_bit_identical_after_recovery"] is True
    assert out["injected_events"] == ["crash_after"]
    assert out["plan_seed"] == 1234
    assert out["surviving_chain"] == ["s0", "s2"]
    assert out["reshard_seconds"] is not None and out["reshard_seconds"] > 0
    assert (out["crash_to_first_token_seconds"] is not None
            and out["crash_to_first_token_seconds"] > 0)
    assert out["chaos_seconds"] > 0 and out["clean_seconds"] > 0


@pytest.mark.slow
def test_leg_disagg_structure_tiny():
    """The disagg leg's CPU dryrun (the ISSUE-8 acceptance shape):
    TTFT p95 under concurrent decode load for colocated vs
    disaggregated, with the disaggregated configuration WINNING on the
    loopback soak, ``dwt_kvcache_h2d_bytes_total`` staying 0 on the
    decode side for migrated pages (device-to-device adopt, no host
    bounce), migrated/adopted page parity, and zero page leaks on
    both pools."""
    out = bench._leg_disagg("llama-test", n_req=3, prompt_len=128,
                            prefill_chunk=8, max_seq=1024,
                            block_tokens=8)
    assert "error" not in out
    colo, dis = out["colocated"], out["disagg"]
    assert colo["requests"] == dis["requests"] == 3
    assert colo["ttft_p95_ms"] > 0 and dis["ttft_p95_ms"] > 0
    # the headline gate: disaggregation beats colocated TTFT p95 under
    # the saturated-decode load (7 of 8 slots pinned)
    assert out["disagg_wins_ttft_p95"] is True
    assert dis["ttft_p95_ms"] < colo["ttft_p95_ms"]
    # migration really happened, page-for-page
    assert dis["migrated_pages"] > 0
    assert dis["adopted_pages"] == dis["migrated_pages"]
    assert dis["migrated_bytes"] > 0
    # zero host bounce on the decode side; zero leaks on both pools
    assert dis["decode_h2d_bytes"] == 0
    assert dis["decode_pool_leaked_blocks"] == 0
    assert dis["prefill_pool_leaked_blocks"] == 0


@pytest.mark.slow
def test_leg_gateway_routing_structure_tiny():
    """The gateway leg's CPU dryrun (the ISSUE-10 acceptance shape):
    cache-aware routing beats round-robin on BOTH prefix hit-rate and
    TTFT p95 over the grouped shared-prefix workload, and the
    mid-soak replica kill completes every request bit-identically (or
    sheds cleanly) with the eviction counter moving."""
    # shape note: the TTFT-p95 gate is structural only when the
    # full-prefill fraction straddles the percentile — round-robin
    # first-touches every (replica, group) pair (3x2 = 15% of 40
    # requests, above p95), cache-aware only every group (2 = 5%,
    # below it) — so per_group is the lever that de-noises the gate,
    # and prefix_len=300 puts the skipped prefill in the 512-wide
    # bucket where it costs something CPU-visible
    out = bench._leg_gateway_routing("llama-test", groups=2, per_group=20,
                                     prefix_len=300, suffix_len=8,
                                     new_tokens=4, slots=2, max_seq=512,
                                     block_tokens=16, kill_requests=4)
    assert "error" not in out
    rr, aw = out["round_robin"], out["cache_aware"]
    assert rr["requests"] == aw["requests"] == 40
    assert rr["ttft_p95_ms"] > 0 and aw["ttft_p95_ms"] > 0
    # round-robin scatters group members, so its gateway-visible hit
    # rate stays at (near) zero while cache-aware sticks the group
    assert aw["prefix_hit_rate"] > rr["prefix_hit_rate"]
    assert aw["reused_prefix_tokens"] > 0
    # the §16 headline gates, as pinned booleans
    assert out["cache_aware_wins_hit_rate"] is True
    assert out["cache_aware_wins_ttft_p95"] is True
    # the chaos phase: no hangs, no divergent tokens, debounce fired
    kl = out["kill"]
    assert kl["requests"] == 4
    assert kl["hung_or_failed"] == 0
    assert out["kill_zero_hangs"] is True
    assert out["kill_bit_identical"] is True
    assert out["kill_replica_down_moved"] is True
    # the survivor fleet kept serving: at least one replica stayed up
    assert len(kl["survivors"]) >= 1


@pytest.mark.slow
def test_leg_stream_failover_structure_tiny():
    """The stream_failover leg's CPU dryrun (the ISSUE-20 acceptance
    shape): a replica dying mid-soak loses NOTHING — every stream
    completes bit-identically to the unfailed reference via gateway
    resume, the SLO ledger books the replay as a resume pause, the
    documented error-line fallback stays reachable at resume_limit=0,
    and both the survivor and the dead path hand their pages back."""
    out = bench._leg_stream_failover("llama-test", n_req=4,
                                     prompt_len=32, new_tokens=8,
                                     slots=2, max_seq=256,
                                     block_tokens=8, crash_after=2,
                                     seed_victim=2)
    assert "error" not in out
    fo = out["failover"]
    assert fo["requests"] == 4 and fo["completed"] == 4
    assert out["failover_completed_100pct"] is True
    assert out["failover_bit_identical"] is True
    # the victim served >=2 pinned streams, each died 2 tokens in, and
    # every death resumed exactly once on the survivor
    assert out["resume_all_succeeded"] is True
    assert fo["resume_attempts"] >= 2
    assert fo["resume_ttf_p95_ms"] is not None
    assert fo["resume_ttf_p95_ms"] > 0
    # the ledger saw the same resumes the gateway counted, and the
    # timeline decomposition still sums exactly
    assert out["slo_books_resume"] is True
    assert fo["slo_resume_pause_p95_ms"] > 0
    # pre-§23 contract still reachable and documented
    assert out["loss_documented_at_limit_0"] is True
    assert 1 <= out["documented_loss"]["delivered_before_error"] < 8
    # zero leaks on both the dead path and the survivor
    assert out["zero_leak_survivor"] is True
    assert out["zero_leak_victim"] is True


# tier-1 budget: run_leg plumbing keeps its quick reps in the micro-
# variants and dispatch-profile tests; this full-budget structure twin
# rides the slow lane
@pytest.mark.slow
def test_leg_long_context_sp_full_budget_structure(monkeypatch):
    """The promoted >=32k sequence-parallel leg (carried VERDICT
    satellite now at FULL budget in the headline order): run_leg
    dispatches it, both strategies report a number, and the micro
    variant still rides the prepass."""
    monkeypatch.setenv("BENCH_LONG_CTX_SP", "256")
    p = {"model": "llama-test", "batch": 2, "prompt_len": 32,
         "new_tokens": 8, "flagship": "llama-test"}
    out = bench.run_leg("long_context_sp", p, micro=True)
    assert "error" not in out
    assert [pt["strategy"] for pt in out["points"]] == ["ring",
                                                        "ulysses"]
    for pt in out["points"]:
        assert "error" not in pt, pt
        assert pt["sp"] == 2 and pt["tokens_per_sec"] > 0


@pytest.mark.slow
def test_leg_prefix_reuse_structure_tiny():
    """The prefix_reuse leg's full structure (cache-off run, cache-on
    run, hit/reuse/saved report) at CPU-viable scale — the dryrun that
    spends tier-1 minutes so the leg can't burn a TPU session attempt
    on a structural bug."""
    out = bench._leg_prefix_reuse("llama-test", 4, slots=2, n_req=4,
                                  shared_len=12, tail_len=4,
                                  block_tokens=4, kv_blocks=16)
    assert "error" not in out
    # every timed request shares the primed 12-token prefix: all hits
    assert out["hit_rate"] == 1.0
    # 3 whole blocks of shared prefix per request
    assert out["reused_tokens"] == out["requests"] * 12
    assert out["tokens_per_sec_cold"] > 0
    assert out["tokens_per_sec_warm"] > 0
    # wall-delta field is present and finite (sign not asserted: at toy
    # scale scheduler noise can swamp the saved prefill)
    assert isinstance(out["prefill_seconds_saved"], float)
    assert out["blocks_resident"] <= 16


@pytest.mark.slow
def test_leg_tiered_prefix_structure_tiny():
    """The tiered_prefix leg's CPU dryrun (the §21 acceptance shape):
    both phases report TTFT percentiles over the measured revisit
    rounds, promotion h2d bytes move (and the re-prefill phase's stay
    0), blocks demote/spill/promote through all three tiers, the
    greedy revisit tokens are bit-identical across phases, and the
    three-tier zero-leak gate holds at leg end.  The micro shape is
    the run_leg --micro one: a 14-block pool under a 4-group working
    set with a 2-group host ring, so the rest round-trips through the
    disk segment.  The TTFT-p95 WIN is asserted by the full-shape leg
    on device (at this toy scale a 56-token re-prefill costs less than
    the promote dispatch), not here — structure only."""
    out = bench.run_leg("tiered_prefix",
                        {"model": "llama-test", "batch": 2,
                         "prompt_len": 32, "new_tokens": 8,
                         "flagship": "llama-test"}, micro=True)
    assert "error" not in out
    assert out["micro"] is True
    a, b = out["reprefill"], out["tiered"]
    # measured wave = (revisits - 1) rounds x groups
    assert a["requests"] == b["requests"] == 4
    assert a["ttft_p95_ms"] >= a["ttft_p50_ms"] > 0
    assert b["ttft_p95_ms"] >= b["ttft_p50_ms"] > 0
    assert out["tiered_wins_ttft_p95"] in (True, False)
    # the promotion path moved real bytes; nothing else may touch the
    # host bounce (the re-prefill phase pins the counter at 0)
    assert out["promote_h2d_bytes"] > 0
    assert out["reprefill_h2d_bytes"] == 0
    # all three tiers exercised: demotions filled the host ring, the
    # overflow spilled to the disk segment, and revisits promoted back
    # from BOTH
    assert out["demoted_blocks"] > 0
    assert out["spilled_blocks"] > 0
    assert out["promoted_blocks"] > 0
    assert out["tier_hits"]["host"] > 0
    assert out["tier_hits"]["disk"] > 0
    share = out["tier_hit_share"]
    assert abs(share["host"] + share["disk"] - 1.0) < 0.01
    # pinned greedy bit-identity: a promoted prefix is the same cache
    # state, token for token
    assert out["bit_identical"] is True
    # and nothing leaked in any tier
    assert out["three_tier_zero_leak"] is True
    assert out["leaked_blocks"] == {"reprefill": 0, "tiered": 0}


@pytest.mark.slow
def test_leg_decode_fused_structure_tiny():
    """The decode_fused leg's full structure (per-point engines across
    batch x stream_block K, measured dispatches/token) at CPU-viable
    scale — and the leg-level acceptance shape: K=1 pays exactly one
    dispatch per token, K=4 pays 1/K (no eos in the synthetic prompt
    stream, so the ratio is exact)."""
    out = bench._leg_decode_fused("llama-test", 8, 8,
                                  batches=(1, 2), blocks=(1, 4))
    assert "error" not in out
    assert len(out["points"]) == 4
    for pt in out["points"]:
        assert "error" not in pt, pt
        assert pt["tokens"] == 8
        assert pt["decode_tokens_per_sec"] > 0
        K = pt["stream_block"]
        assert pt["host_dispatches"] == (8 if K == 1 else 2)
        assert pt["dispatches_per_token"] == (1.0 if K == 1 else 0.25)
        assert pt["device_loop_steps"] == 8
    assert out["best_decode_tokens_per_sec"] > 0


@pytest.mark.slow
def test_leg_mixed_batching_gates_tiny():
    """The §19 acceptance leg at the de-noised CPU shape: mixed
    token-budget dispatch must strictly beat the alternating baseline
    on aggregate tok/s at equal-or-better TTFT p95, with the 1/K
    structural signature on dispatches/step.  The shape is the one
    run_leg pins for --micro: chunk-heavy prompts through one free
    slot while three background rows decode, all arrivals at once —
    admission pressure covers the whole measured window, which is
    where the baseline's fused-loop suppression costs and the mixed
    packing pays."""
    K = 4
    out = bench._leg_mixed_batching("llama-test", prompt_len=96,
                                    new_tokens=16, slots=4, n_req=8,
                                    prefill_chunk=8, decode_block=K,
                                    arrival_s=0.0, block_tokens=8)
    assert "error" not in out
    assert out["token_budget"] == 4 * K + 2 * 8
    base, mixed = out["baseline"], out["mixed"]
    for mode in (base, mixed):
        assert mode["tokens_per_sec"] > 0
        assert mode["ttft_p95_ms"] is not None
        assert mode["leaked_blocks"] == 0
    # every prompt token of the measured stream went through a packed
    # prefill segment
    assert mixed["prefill_tokens"] == 8 * 96
    assert mixed["mixed_dispatches"] > 0
    assert 0.0 < mixed["budget_utilization"] <= 1.5
    # the structural signature: mixed keeps the fused decode cadence
    # under admission (~1/K dispatches/step); the baseline's
    # suppression drags it toward per-token dispatch
    assert mixed["dispatches_per_step"] <= 1 / K + 0.12, mixed
    assert base["dispatches_per_step"] > mixed["dispatches_per_step"] * 2
    # the acceptance gates (3/3 stable on CPU at this shape)
    assert out["mixed_wins_tokens_per_sec"] is True, (base, mixed)
    assert out["mixed_ttft_p95_le_baseline"] is True, (base, mixed)


@pytest.mark.slow
def test_leg_spec_mixed_structure_tiny():
    """The §22 acceptance leg at the run_leg --micro shape: three
    engines (spec-only serialized chunks, mixed-only packer, fused
    spec x mixed) over the same motif-tiled arrival stream.  On CPU the
    leg must hold its STRUCTURE: the fused arm keeps the 1/K dispatch
    cadence (vs the spec-only arm's ~1/round serialization), carries
    every prompt token through packed segments, reports the §22 shrink
    observables, and leaks nothing in any arm.  The throughput gate is
    asserted (the fused program beats both single-feature arms even
    compute-bound); the TTFT gate is asserted present-and-boolean only
    — spec pricing shrinks per-dispatch prefill room, which CPU pays in
    compute where TPU streams it from HBM."""
    K = 4
    out = bench._leg_spec_mixed("llama-test", prompt_len=96,
                                new_tokens=8, slots=4, n_req=6,
                                prefill_chunk=8, decode_block=K,
                                num_draft=2, arrival_s=0.0,
                                block_tokens=8)
    assert "error" not in out
    # §22 pricing: the default budget prices every slot at
    # (K_row + 1) * decode_block plus two chunks of prefill room
    assert out["token_budget"] == 4 * (2 + 1) * K + 2 * 8
    spec_only, mixed_only, fused = (out["spec_only"], out["mixed_only"],
                                    out["spec_mixed"])
    for mode in (spec_only, mixed_only, fused):
        assert mode["tokens_per_sec"] > 0
        assert mode["ttft_p95_ms"] is not None
        assert mode["leaked_blocks"] == 0
    # every prompt token of the measured stream went through a packed
    # prefill segment in BOTH mixed arms
    assert mixed_only["prefill_tokens"] == 6 * 96
    assert fused["prefill_tokens"] == 6 * 96
    assert 0.0 < fused["budget_utilization"] <= 1.5
    # the structural signature: the fused program keeps the 1/K fused
    # cadence WITH speculation aboard; the spec-only arm pays ~one
    # dispatch per speculative round
    assert fused["dispatches_per_step"] <= 1 / K + 0.12, fused
    assert (spec_only["dispatches_per_step"]
            > fused["dispatches_per_step"] * 2)
    # §22 shrink observables ride both spec arms
    for arm in (spec_only, fused):
        sp = arm["spec"]
        assert sp["drafted"] > 0 and sp["adaptive"] is True
        assert set(sp["k_row_buckets"]) == {"1", "2"}
    # the background rows survive the window (a row finishing
    # mid-window would dump its warmup-compile TTFT into the reservoir
    # and zero its arm's background tokens)
    assert fused["background_tokens"] > 0
    assert sum(fused["spec"]["k_row_buckets"].values()) == 3
    # the throughput gate holds even compute-bound; the TTFT gate is a
    # measured boolean whose truth is a device property
    assert out["spec_mixed_wins_tokens_per_sec"] is True, out
    assert isinstance(out["ttft_p95_le_mixed_only"], bool)


def test_run_leg_stamps_dispatch_profile_extras(monkeypatch):
    """The §20 bench satellite's CPU dryrun: a headline-order leg run
    through run_leg stamps the ``dispatch_profile`` extras block —
    per-signature p50/p95 from the sampled dispatch profiler plus the
    compile ledger — so bench artifacts carry the cost
    observatory without a TPU session proving the plumbing first.
    Sampling is forced to every dispatch so the tiny micro shape still
    banks samples deterministically."""
    from distributed_inference_demo_tpu.telemetry import profiling
    monkeypatch.setenv("DWT_PROFILE_SAMPLE_N", "1")
    profiling.reset_observatory()
    try:
        p = {"model": "llama-test", "batch": 8, "prompt_len": 64,
             "new_tokens": 128, "flagship": "llama-test"}
        out = bench.run_leg("decode_fused", p, micro=True)
        assert "error" not in out
        dp = out["dispatch_profile"]
        assert dp["sample_n"] == 1
        # the K=4 point runs the fused loop: its signature carries the
        # program class, pow2 batch bucket, chunk K and kv dtype
        sigs = dp["signatures"]
        assert any(s.startswith("decode_loop|b1|c4|") for s in sigs), sigs
        for entry in sigs.values():
            assert entry["samples"] >= 1
            assert entry["dispatches"] >= entry["samples"]
            assert entry["p95_ms"] >= entry["p50_ms"] >= 0.0
        # the compile ledger saw the engine's jitted programs compile
        comp = dp["compile"]
        assert comp["decode_loop"]["compiles"] >= 1
        assert comp["decode_loop"]["compile_seconds"] > 0
        # un-budgeted programs must not feed recompile_storm
        assert comp["decode_loop"]["variant_budget"] is None
    finally:
        monkeypatch.delenv("DWT_PROFILE_SAMPLE_N", raising=False)
        profiling.reset_observatory()


def test_run_leg_micro_variants_stamp_and_shrink():
    """--micro runs the same leg structure at the smallest meaningful
    shape and stamps the result so a micro number can never masquerade
    as a full-budget measurement."""
    p = {"model": "llama-test", "batch": 8, "prompt_len": 64,
         "new_tokens": 128, "flagship": "llama-test"}
    shrunk = bench.micro_shape(p)
    assert (shrunk["batch"], shrunk["prompt_len"],
            shrunk["new_tokens"]) == (2, 32, 8)
    out = bench.run_leg("decode_fused", p, micro=True)
    assert out["micro"] is True
    assert out["micro_shape"] == {"batch": 2, "prompt_len": 32,
                                  "new_tokens": 8}
    assert "error" not in out
    # the micro decode_fused variant runs the reduced point grid
    assert {(pt["batch"], pt["stream_block"])
            for pt in out["points"]} == {(1, 1), (1, 4)}


def test_headline_summary_null_when_not_comparable():
    # a different batch than the stored CPU baseline must report null,
    # never a mislabeled multiplier
    s = bench.headline_summary(
        {"decode_tokens_per_sec": 100.0, "dtype": "bf16"},
        {"model": "tinyllama-1.1b", "batch": 999, "prompt_len": 64,
         "new_tokens": 128, "flagship": "f"}, "dev")
    assert s["value"] == 100.0 and s["vs_baseline"] is None


def test_multichip_render_matches_driver_bytes():
    """The driver rewrites MULTICHIP artifacts from parsed JSON in its
    own format; tools/record_multichip.render_artifact must reproduce a
    driver-written file BYTE-IDENTICALLY (no git_head field, no trailing
    newline) or every re-run shows the artifact dirty."""
    import importlib.util
    import json
    spec = importlib.util.spec_from_file_location(
        "record_multichip", REPO / "tools" / "record_multichip.py")
    rm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rm)
    raw = (REPO / "MULTICHIP_r05.json").read_text()
    parsed = json.loads(raw)
    rendered = rm.render_artifact(parsed["n_devices"], parsed["rc"],
                                  parsed["tail"],
                                  skipped=parsed["skipped"])
    assert rendered == raw
    assert not rendered.endswith("\n")
    assert "git_head" not in rendered
