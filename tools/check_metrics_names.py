#!/usr/bin/env python
"""Metric-name lint: every registered Prometheus series must follow the
repo convention (docs/DESIGN.md §7).

Rules, checked against the default registry after importing
``telemetry.catalog`` (which registers the full standard set at import
time):

1. names are ``dwt_<subsystem>_<rest>`` — the ``dwt_`` prefix namespaces
   the repo and ``<subsystem>`` must be a known subsystem;
2. the name ends in a recognized unit suffix (counters may follow the
   unit with Prometheus's ``_total``); dimensionless gauges must say so
   (``_ratio`` / bare count units like ``_slots``);
3. every metric has non-empty help text (enforced structurally by
   ``metrics.Metric`` — re-checked here so a future constructor bypass
   still fails the lint);
4. counters end in ``_total``; non-counters must NOT (the Prometheus
   convention scrapers and recording rules rely on);
5. label sets are linted too: label names come from a known vocabulary
   (a typo'd label forks a series family no dashboard joins), the
   fleet-plane families' label sets are pinned exactly
   (``tenant=``/``replica=`` must stay catalog-declared), and
   ``replica`` is reserved for the ``/metrics/fleet`` relabeler on
   non-gateway series.

Run standalone (``python tools/check_metrics_names.py``, exit 1 on
violations) or via the tier-1 suite (``tests/test_metrics_names.py``).
"""

from __future__ import annotations

import sys
from typing import List

SUBSYSTEMS = {"stage", "batching", "speculative", "http", "monitor",
              "engine", "control", "anomaly", "flight", "kvcache",
              "transport", "fault", "disagg", "gateway", "migration",
              "slo", "profile", "compile", "hbm"}

# unit suffixes a metric name may end with (after stripping ``_total``).
# Plain-count units (requests, tokens, ...) double as the unit for
# occupancy gauges (queue depth in requests, capacity in slots).
UNITS = {"seconds", "bytes", "messages", "steps", "tokens", "requests",
         "rounds", "hits", "misses", "slots", "spans", "entries",
         "ratio", "bytes_per_second", "flops_per_second", "celsius",
         "info", "events", "bundles", "blocks", "nodes",
         "retries", "reconnects", "frames", "faults", "dispatches",
         "pages", "replicas", "scrapes", "samples", "attempts",
         "failures"}

# label names any series may declare.  The label VOCABULARY is linted
# like the name vocabulary: a typo'd label ("tenent", "repilca") would
# silently fork a series family that no dashboard joins, which is worse
# than a crash.  Extend deliberately, with the catalog.
KNOWN_LABELS = {"role", "device", "route", "code", "kind", "engine",
                "peer", "replica", "dtype", "tenant", "window",
                "signature", "program", "owner", "tier", "bucket",
                "reason"}

# series whose label SET is pinned exactly — the fleet-plane families
# whose labels dashboards and the federation relabeler join on.  A
# tenant series silently losing its tenant label (or growing a stray
# one) would still render, still scrape, and aggregate every tenant
# into one line — this lint makes that drift a tier-1 failure.
REQUIRED_LABELS = {
    "dwt_slo_ttft_seconds": ("tenant",),
    "dwt_slo_queue_wait_seconds": ("tenant",),
    "dwt_slo_per_token_seconds": ("tenant",),
    "dwt_slo_e2e_seconds": ("tenant",),
    "dwt_slo_migration_pause_seconds": ("tenant",),
    "dwt_slo_requests_total": ("tenant",),
    "dwt_slo_failed_requests_total": ("tenant",),
    "dwt_slo_tokens_total": ("tenant",),
    "dwt_slo_good_tokens_total": ("tenant",),
    "dwt_slo_good_ttft_requests_total": ("tenant",),
    "dwt_slo_migrated_requests_total": ("tenant",),
    "dwt_slo_burn_rate_ratio": ("tenant", "window"),
    "dwt_gateway_fleet_scrapes_total": ("replica",),
    "dwt_gateway_fleet_failed_scrapes_total": ("replica",),
    "dwt_gateway_fleet_scrape_age_seconds": ("replica",),
    "dwt_gateway_prefix_hit_ratio": ("replica",),
    "dwt_gateway_index_entries": ("replica",),
    "dwt_gateway_queue_depth_requests": ("replica",),
    "dwt_anomaly_events_total": ("kind",),
    "dwt_anomaly_last_seconds": ("kind",),
    # cost observatory (docs/DESIGN.md §20): the dispatch-signature /
    # program / owner keys ARE the join keys the auto-planner and
    # fleet_top --profile aggregate on — losing one collapses every
    # program variant (or pool owner) into a single meaningless line
    "dwt_profile_dispatch_seconds": ("signature",),
    "dwt_profile_samples_total": ("signature",),
    "dwt_profile_dispatches_total": ("signature",),
    "dwt_profile_achieved_bytes_per_second": ("signature",),
    "dwt_profile_roofline_ratio": ("signature",),
    "dwt_compile_events_total": ("program",),
    "dwt_compile_seconds_total": ("program",),
    "dwt_compile_cache_entries": ("program",),
    "dwt_compile_variant_budget_entries": ("program",),
    "dwt_hbm_owner_bytes": ("owner",),
    "dwt_hbm_watermark_bytes": ("owner",),
    # tiered KV (docs/DESIGN.md §21): the tier label (host / disk) is
    # what separates "RAM is full" from "disk is full" on a dashboard —
    # an unlabeled residency gauge would sum the two budgets into one
    # meaningless number
    "dwt_kvcache_tier_resident_bytes": ("tier",),
    "dwt_kvcache_tier_resident_blocks": ("tier",),
    "dwt_kvcache_tier_capacity_bytes": ("tier",),
    "dwt_kvcache_tier_hits_total": ("tier",),
    # zero-loss streams (docs/DESIGN.md §23): resume pause is a tenant
    # SLO dimension like migration pause, and the failure-reason label
    # is the bounded vocabulary /debugz and dashboards break down on —
    # losing it would fold probe flakes and mid-stream deaths into one
    # undiagnosable count
    "dwt_slo_resume_pause_seconds": ("tenant",),
    "dwt_slo_resumed_requests_total": ("tenant",),
    "dwt_gateway_replica_failures_total": ("reason",),
}

# label names reserved for the federation relabeler: GET /metrics/fleet
# injects replica="<rid>" into every replica-exported sample, so a
# REPLICA-side series already carrying the label would collide with the
# injected one (Prometheus rejects duplicate label names in a sample).
# Gateway-side series (dwt_gateway_*) legitimately declare it — they
# are emitted by the gateway's own registry, never relabeled.
FEDERATION_RESERVED_LABELS = {"replica"}

# exact names exempted from the unit-suffix rule — each entry is a
# deliberate, documented exception (NOT a new unit: adding a pseudo-unit
# would let every future misnamed series ending the same way slip
# through).  dwt_kvcache_blocks_in_use carries its unit (blocks) mid-
# name; it pairs with dwt_kvcache_used_blocks as the all-owners gauge
# (docs/DESIGN.md §11 runbook).  The gateway replica-transition pair
# carries its unit (replicas) mid-name too: the ISSUE-10 acceptance
# pins the exact name dwt_gateway_replica_down_total, and up/down name
# the transition direction where the unit would sit.
UNIT_SUFFIX_EXEMPT = {"dwt_kvcache_blocks_in_use",
                      "dwt_gateway_replica_down_total",
                      "dwt_gateway_replica_up_total",
                      # ISSUE-15 pins this exact name: a dimensionless
                      # packed/budgeted fraction (a _ratio in spirit;
                      # "utilization" is the roofline-adjacent term the
                      # §19 runbook uses; the benchmark's
                      # sched_budget_util_pct is the same fraction)
                      "dwt_batching_token_budget_utilization",
                      # ISSUE-19 pins this exact name: the per-bucket
                      # adaptive-K occupancy gauge — "len" is the
                      # quantity itself (a draft LENGTH bucket), the
                      # value's unit is rows via the bucket label
                      "dwt_batching_draft_len",
                      # ISSUE-20 pins this exact name: the resumes that
                      # finished the stream — "succeeded" names the
                      # outcome where the unit would sit, pairing with
                      # dwt_gateway_resume_attempts_total
                      "dwt_gateway_resume_succeeded_total"}

# series the catalog must always register (regressions here would blind
# the flight-recorder/anomaly layer silently — a scrape with the series
# simply absent looks exactly like a healthy quiet system).  The
# dwt_kvcache_* block is required the same way: a serving stack whose
# cache section vanished from /metrics reads as "cache disabled", which
# is indistinguishable from "prefix reuse silently regressed".
REQUIRED_SERIES = {
    "dwt_flight_events_total",
    "dwt_flight_buffer_events",
    "dwt_anomaly_events_total",
    "dwt_anomaly_last_seconds",
    "dwt_anomaly_postmortem_bundles_total",
    "dwt_kvcache_hits_total",
    "dwt_kvcache_misses_total",
    "dwt_kvcache_partial_hit_tokens_total",
    "dwt_kvcache_stored_blocks_total",
    "dwt_kvcache_evicted_blocks_total",
    "dwt_kvcache_resident_bytes",
    "dwt_kvcache_tree_nodes",
    # the paged-layout triple (docs/DESIGN.md §11): device residency and
    # the h2d counter whose staying-at-zero IS the paged path's claim —
    # their absence would make "zero-copy prefix hits" unverifiable
    "dwt_kvcache_device_resident_bytes",
    "dwt_kvcache_blocks_in_use",
    "dwt_kvcache_h2d_bytes_total",
    "dwt_kvcache_page_dtype_info",
    "dwt_kvcache_quant_scale_bytes",
    # the §21 tier triple: residency plus the demote/promote flow
    # counters — a tier silently absent from /metrics reads as
    # "tiering disabled", indistinguishable from "demotions regressed"
    "dwt_kvcache_tier_resident_bytes",
    "dwt_kvcache_tier_promoted_blocks_total",
    "dwt_kvcache_tier_demoted_blocks_total",
    # the transport-reliability / chaos quartet (docs/DESIGN.md §12): a
    # corrupt frame that is silently absent from /metrics is exactly the
    # "decoded garbage into a wrong token" failure this layer exists to
    # rule out, and dwt_fault_* staying registered-and-zero is how a
    # production scrape PROVES no fault plan leaked into the process
    "dwt_transport_send_retries_total",
    "dwt_transport_reconnects_total",
    "dwt_transport_corrupt_frames_total",
    "dwt_fault_injected_faults_total",
    # the mixed-dispatch triple (docs/DESIGN.md §19): utilization absent
    # would make "the budget is actually being packed" unverifiable, and
    # mixed_dispatches staying registered-and-zero is how a scrape PROVES
    # an engine is running the serialized interleave, not mixed mode
    "dwt_batching_mixed_dispatches_total",
    "dwt_batching_mixed_prefill_tokens_total",
    "dwt_batching_token_budget_utilization",
    # the spec-in-the-batch quartet (docs/DESIGN.md §22): drafted /
    # accepted absent would make the acceptance collapse the adaptive-K
    # loop reacts to unobservable, and the draft_len bucket gauge
    # registered-and-zero is how a scrape PROVES no row is speculating
    "dwt_batching_draft_tokens_total",
    "dwt_batching_accepted_tokens_total",
    "dwt_batching_draft_len",
    "dwt_batching_spec_acceptance_ratio",
    # the device-loop pair (docs/DESIGN.md §13): dispatches/token ≈ 1/K
    # is the dispatch-floor claim — with either series absent, a fused
    # loop that silently fell back to per-token dispatch would scrape
    # exactly like a healthy one
    "dwt_engine_host_dispatches_total",
    "dwt_engine_device_loop_steps_total",
    # the disaggregation set (docs/DESIGN.md §15): migrated vs adopted
    # pages diverging is the wedged-handoff signal, and rescheduled
    # staying registered-and-zero is how a scrape PROVES no prefill
    # worker silently died mid-migration
    "dwt_disagg_migrated_pages_total",
    "dwt_disagg_migrated_bytes_total",
    "dwt_disagg_adopted_pages_total",
    "dwt_disagg_rescheduled_requests_total",
    "dwt_disagg_migration_seconds",
    "dwt_disagg_handoff_queue_depth_requests",
    # the gateway set (docs/DESIGN.md §16): replica_down staying
    # registered-and-zero is how a scrape PROVES no replica was
    # evicted, and routed/hashed/retried absent would make the
    # cache-aware-vs-fallback split (the subsystem's whole point)
    # unobservable
    "dwt_gateway_prefix_routed_requests_total",
    "dwt_gateway_hashed_requests_total",
    "dwt_gateway_retried_requests_total",
    "dwt_gateway_shed_requests_total",
    "dwt_gateway_replica_down_total",
    "dwt_gateway_replica_up_total",
    "dwt_gateway_up_replicas",
    "dwt_gateway_proxy_ttft_seconds",
    # draining (docs/DESIGN.md §18): a drain whose gauge vanished from
    # /metrics reads as "nothing draining" — exactly the stuck-drain
    # incident the gauge exists to surface
    "dwt_gateway_draining_replicas",
    # the live-migration set (docs/DESIGN.md §18): exported vs imported
    # diverging is the failed-admission signal, replayed staying
    # registered-and-zero is how a scrape PROVES the atomic handoff
    # never re-emitted a step to a client, and inflight stuck nonzero
    # names a wedged migration path
    "dwt_migration_exported_requests_total",
    "dwt_migration_imported_requests_total",
    "dwt_migration_aborted_requests_total",
    "dwt_migration_replayed_steps_total",
    "dwt_migration_moved_pages_total",
    "dwt_migration_moved_bytes_total",
    "dwt_migration_handoff_seconds",
    "dwt_migration_inflight_requests",
    # the fleet observability plane (docs/DESIGN.md §7): per-tenant SLO
    # accounting absent from a scrape is indistinguishable from "no
    # tenant ever violated its SLO", and the federation counters absent
    # would make a dead replica's section silently vanish from
    # /metrics/fleet with nothing left to alert on
    "dwt_slo_requests_total",
    "dwt_slo_tokens_total",
    "dwt_slo_good_tokens_total",
    "dwt_slo_ttft_seconds",
    "dwt_slo_per_token_seconds",
    "dwt_slo_e2e_seconds",
    "dwt_slo_migration_pause_seconds",
    "dwt_slo_burn_rate_ratio",
    "dwt_gateway_fleet_scrapes_total",
    "dwt_gateway_fleet_failed_scrapes_total",
    "dwt_gateway_fleet_scrape_age_seconds",
    # the cost observatory (docs/DESIGN.md §20): dispatches_total
    # registered-and-zero is how a scrape PROVES sampling is off (the
    # free off-path), compile_events absent would let a recompile storm
    # burn the fleet with nothing to alert on, and the HBM watermark
    # vanishing reads as "pools never grew" — exactly the OOM-postmortem
    # blindness the ledger exists to end
    "dwt_profile_dispatch_seconds",
    "dwt_profile_samples_total",
    "dwt_profile_dispatches_total",
    "dwt_profile_achieved_bytes_per_second",
    "dwt_profile_roofline_ratio",
    "dwt_compile_events_total",
    "dwt_compile_seconds_total",
    "dwt_compile_cache_entries",
    "dwt_compile_variant_budget_entries",
    "dwt_hbm_owner_bytes",
    "dwt_hbm_watermark_bytes",
    # zero-loss streams (docs/DESIGN.md §23): attempts/succeeded
    # diverging is the failed-failover signal, resumed_requests
    # registered-and-zero is how a scrape PROVES no stream needed a
    # survivor, and the diverged counter absent would let a journal the
    # survivor cannot reproduce fail invisibly — the one failure mode
    # the verify queue exists to make loud
    "dwt_gateway_resume_attempts_total",
    "dwt_gateway_resume_succeeded_total",
    "dwt_gateway_resume_exhausted_requests_total",
    "dwt_gateway_replica_failures_total",
    "dwt_batching_resumed_requests_total",
    "dwt_batching_resume_diverged_requests_total",
    "dwt_slo_resume_pause_seconds",
    "dwt_slo_resumed_requests_total",
}


def check_registry(registry) -> List[str]:
    """Return a list of human-readable violations (empty = clean)."""
    problems: List[str] = []
    for m in registry.collect():
        name = m.name
        if not getattr(m, "help", "").strip():
            problems.append(f"{name}: missing help text")
        parts = name.split("_")
        if parts[0] != "dwt" or len(parts) < 3:
            problems.append(
                f"{name}: must be dwt_<subsystem>_<name>_<unit>")
            continue
        if parts[1] not in SUBSYSTEMS:
            problems.append(
                f"{name}: unknown subsystem {parts[1]!r} (known: "
                f"{sorted(SUBSYSTEMS)})")
        is_counter = getattr(m, "type", "") == "counter"
        stripped = parts[:-1] if parts[-1] == "total" else parts
        if is_counter and parts[-1] != "total":
            problems.append(f"{name}: counters must end in _total")
        if not is_counter and parts[-1] == "total":
            problems.append(
                f"{name}: _total is reserved for counters "
                f"(type is {m.type!r})")
        # unit may be one or two tokens (bytes_per_second)
        unit1 = stripped[-1]
        unit3 = "_".join(stripped[-3:]) if len(stripped) >= 3 else ""
        if (unit1 not in UNITS and unit3 not in UNITS
                and name not in UNIT_SUFFIX_EXEMPT):
            problems.append(
                f"{name}: missing unit suffix (allowed: {sorted(UNITS)})")
        # label-set lint: vocabulary, pinned sets, federation reserve
        labels = tuple(getattr(m, "label_names", ()) or ())
        for lab in labels:
            if lab not in KNOWN_LABELS:
                problems.append(
                    f"{name}: unknown label {lab!r} (known: "
                    f"{sorted(KNOWN_LABELS)})")
        want = REQUIRED_LABELS.get(name)
        if want is not None and tuple(sorted(labels)) != tuple(
                sorted(want)):
            problems.append(
                f"{name}: label set {sorted(labels)} must be exactly "
                f"{sorted(want)}")
        if (parts[1] != "gateway"
                and FEDERATION_RESERVED_LABELS & set(labels)):
            problems.append(
                f"{name}: label(s) "
                f"{sorted(FEDERATION_RESERVED_LABELS & set(labels))} are "
                "reserved for the /metrics/fleet relabeler (replica-side "
                "series must not pre-declare them)")
    return problems


# series that must NOT exist: the dwt_batching_prefix_* aliases were
# deprecated in PR 3 ("one release") and removed three releases later —
# re-registering one would resurrect a name dashboards already migrated
# off, so absence is linted like presence (docs/DESIGN.md §10 runbook)
FORBIDDEN_SERIES = {
    "dwt_batching_prefix_cache_hits_total",
    "dwt_batching_prefix_cache_misses_total",
    "dwt_batching_prefix_reused_tokens_total",
}


def check_required(registry) -> List[str]:
    """Presence lint for the standard catalog (run against the DEFAULT
    registry only — synthetic test registries legitimately hold other
    series sets)."""
    present = {m.name for m in registry.collect()}
    return ([f"required series {name} is not registered"
             for name in sorted(REQUIRED_SERIES - present)]
            + [f"removed series {name} is registered again (the "
               "deprecated alias was deleted; see FORBIDDEN_SERIES)"
               for name in sorted(FORBIDDEN_SERIES & present)])


def main() -> int:
    # repo root on sys.path when run as a script from anywhere
    import pathlib
    root = str(pathlib.Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from distributed_inference_demo_tpu.telemetry import catalog  # noqa: F401
    from distributed_inference_demo_tpu.telemetry.metrics import REGISTRY

    problems = check_registry(REGISTRY) + check_required(REGISTRY)
    for p in problems:
        print(f"METRIC LINT: {p}", file=sys.stderr)
    if problems:
        print(f"{len(problems)} metric naming violation(s)",
              file=sys.stderr)
        return 1
    n = len(REGISTRY.collect())
    print(f"metric names OK ({n} series checked)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
