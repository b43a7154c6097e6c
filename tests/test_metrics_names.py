"""Tier-1 hook for the metric-name lint (tools/check_metrics_names.py):
the full standard series set (telemetry/catalog) must follow the
``dwt_<subsystem>_<name>_<unit>`` convention with help text on every
metric — a new metric with a bad name fails the suite, not a style
review."""

import importlib.util
import pathlib

import pytest

from distributed_inference_demo_tpu.telemetry import catalog  # noqa: F401
from distributed_inference_demo_tpu.telemetry.metrics import (
    Counter, Gauge, REGISTRY, Registry)


def _load_lint():
    path = (pathlib.Path(__file__).resolve().parents[1] / "tools"
            / "check_metrics_names.py")
    spec = importlib.util.spec_from_file_location("check_metrics_names",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.quick
def test_standard_catalog_is_clean():
    lint = _load_lint()
    problems = lint.check_registry(REGISTRY)
    assert problems == []


def test_required_flight_anomaly_series_registered():
    """The flight-recorder/anomaly series must exist in the standard
    catalog — their absence would read as a healthy quiet system."""
    lint = _load_lint()
    assert lint.check_required(REGISTRY) == []
    names = {m.name for m in REGISTRY.collect()}
    assert "dwt_anomaly_events_total" in names
    assert "dwt_flight_buffer_events" in names


def test_lint_catches_violations():
    """The lint actually fires: a unitless name, a foreign prefix, a
    counter without _total, and a gauge pretending to be a counter all
    produce violations."""
    lint = _load_lint()
    reg = Registry()
    reg.register(Counter("dwt_stage_emitted_tokens_total",
                         "a clean counter"))
    reg.register(Counter("dwt_stage_stuff", "no unit, no total"))
    reg.register(Gauge("foo_bar_seconds", "foreign prefix"))
    reg.register(Gauge("dwt_stage_bad_seconds_total",
                       "gauge claiming _total"))
    problems = lint.check_registry(reg)
    assert not any("dwt_stage_emitted_tokens_total" in p
                   for p in problems)
    assert any("dwt_stage_stuff" in p and "_total" in p
               for p in problems)
    assert any("dwt_stage_stuff" in p and "unit" in p for p in problems)
    assert any("foo_bar_seconds" in p for p in problems)
    assert any("dwt_stage_bad_seconds_total" in p and "reserved"
               in p for p in problems)


def test_lint_requires_help_text():
    """Help text is enforced at construction (MetricError) AND by the
    lint for registries built another way."""
    import pytest

    from distributed_inference_demo_tpu.telemetry.metrics import \
        MetricError
    with pytest.raises(MetricError):
        Counter("dwt_stage_x_bytes_total", "   ")


def test_main_exits_clean():
    lint = _load_lint()
    assert lint.main() == 0


def test_deprecated_prefix_aliases_removed():
    """The dwt_batching_prefix_* aliases (PR 3, 'one release') are gone
    — and the lint guards the tombstone so they can't quietly return."""
    lint = _load_lint()
    names = {m.name for m in REGISTRY.collect()}
    assert not (lint.FORBIDDEN_SERIES & names)
    reg = Registry()
    reg.register(Counter("dwt_batching_prefix_cache_hits_total",
                         "resurrected alias"))
    assert any("registered again" in p for p in lint.check_required(reg))
