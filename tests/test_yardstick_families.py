"""The families' files of ``benchmark/tests`` (``test_*_family.py``: a
configuration's model against its plain reference), a case and a
subprocess each: see ``tests/test_yardstick.py``, which holds the others.

Five of those files assert the exact set of metrics that list their cell,
from before the five metrics that read the dispatch join had ``workloads``
lists (``tests/manifest_less_join_lists.py`` says why they have them now).
Each file runs here without that one case, the case runs on the manifest
less those five lists, and the lists are held below.
"""

import json

import pytest

from manifest_less_join_lists import JOIN_METRICS
from test_yardstick import (BENCH, benchmark_test_files,
                            run_benchmark_test_file)

EXACT_SET_CASES = {
    "test_evabyte_family.py":
        "test_the_manifest_lists_the_cell_where_the_issue_says",
    "test_granite_moe_hybrid_family.py":
        "test_the_manifest_lists_the_cell_where_it_may",
    "test_laguna_family.py":
        "test_the_manifest_lists_the_cell_where_the_issue_says",
    "test_solar_open2_family.py":
        "test_the_manifest_lists_the_cell_where_the_issue_says",
    "test_xing4_0_family.py": "test_the_manifest_lists_the_cell",
}
# the cells the benchmark had when the join metrics were given their lists
# (PR 69), in the manifest's order
ACCEPTED_CELLS = [
    "qwen2.5-7b-int8.chat", "qwen2.5-7b-int8.sessions",
    "bloom7b1-int8.longctx-sat", "qwen2.5-7b-bf16-tp4.chat",
    "olmoe-1b-7b-int8.reason-closed", "ouro-2.6b-bf16.reason-sat",
    "kanana-2-30b-a3b-bf16.longdoc-sat",
    "laguna-s-2.1-bf16-ep4.mixedlen-sat", "evabyte-6.5b-bf16.longdoc-sat",
    "solar-open2-250b-bf16-ep8.reason-wide",
    "xing4.0-29b-a4b-bf16.longdoc-sat",
    "granite-4.0-h-small-bf16-ep2.longdoc-wide",
    "nemotron-3-nano-30b-a3b-bf16-ep2.reason-wide"]


@pytest.mark.parametrize("name", benchmark_test_files(family=True))
def test_a_family_s_file_of_the_benchmarks_own_suite_passes(name):
    case = EXACT_SET_CASES.get(name)
    run_benchmark_test_file(
        name, *(["--deselect", f"benchmark/tests/{name}::{case}"]
                if case else []))


@pytest.mark.parametrize("name", sorted(EXACT_SET_CASES))
def test_an_exact_set_holds_on_the_manifest_less_the_join_lists(name):
    assert name in benchmark_test_files(family=True)
    run_benchmark_test_file(name, "-p", "manifest_less_join_lists",
                            case=EXACT_SET_CASES[name])


@pytest.mark.parametrize("metric", JOIN_METRICS)
def test_a_join_metric_lists_the_cells_it_was_accepted_in(metric):
    """The thirteen cells in which each was reported before it had a list,
    first and in the manifest's order, so that the list changed nothing
    for them; a later cell is appended only where its traced runs print
    the metric."""
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells[:len(ACCEPTED_CELLS)] == ACCEPTED_CELLS
    entry = next(m for m in manifest["per_layer"] if m["name"] == metric)
    assert entry["moves"] == "tpot_p50_ms"
    listed = entry["workloads"]
    assert listed[:len(ACCEPTED_CELLS)] == ACCEPTED_CELLS
    assert set(listed[len(ACCEPTED_CELLS):]) <= set(cells)
    # the cell this PR added prints them only where its trace opened
    # between two executions (one traced run of four, PERF.md section 7)
    assert "minicpm-sala-9b-bf16.longctx-32k" not in listed
