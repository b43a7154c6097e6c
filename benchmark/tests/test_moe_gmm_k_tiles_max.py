"""``moe_gmm_k_tiles_max`` against ``/stats.moe.gmm`` as the program
writes it, and its place in the manifest."""
import json
from pathlib import Path

import pytest

from layer_metrics import moe_gmm_k_tiles_max

BENCH = Path(__file__).resolve().parent.parent
M = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def _line(m, k, tk, tn=768, n=768, tm=64):
    return {"m": m, "k": k, "n": n, "tiles": [tm, tk, tn],
            "tiles_k": k // tk, "rhs_tile_bytes": tk * tn * 2,
            "vmem_limit_bytes": 16 << 20}


@pytest.mark.parametrize("moe, want", [
    # every traced call spans k: a slab's, a decode step's, both ways
    ({"experts": 36, "gmm": [_line(13120, 4096, 4096, tm=256),
                             _line(320, 4096, 4096, tm=32),
                             _line(13120, 768, 768, 1024, 4096)]}, 1),
    # the worst call counts, whatever the others do
    ({"experts": 8, "gmm": [_line(8, 4096, 4096),
                            _line(8, 14336, 3584, 1024, 4096)]}, 4),
    # the parent's program: experts, no entry
    ({"experts": 36, "dispatches": 5}, None),
    # widths that fill no lane: no shape the kernel covers
    ({"experts": 8, "gmm": []}, None),
    # a model without experts has no section
    (None, None)])
def test_reads_the_worst_call_or_nothing(moe, want):
    stats = {"kvcache": {}} if moe is None else {"moe": moe}
    assert moe_gmm_k_tiles_max.read({"stats_close": stats}) == want


def test_the_manifest_lists_it_where_the_accepted_suite_lets_a_cell_join():
    """The layer's name is the accepted one, the metric moves the decode
    latency, and it is listed in the expert cells whose own tests do not
    hold their metric lists closed (PERF.md section 7, left by PR 63)."""
    entry = next(m for m in M["per_layer"]
                 if m["name"] == "moe_gmm_k_tiles_max")
    assert entry == {
        "name": "moe_gmm_k_tiles_max", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms", "workloads": entry["workloads"]}
    cells = {w["name"]: w["config"] for w in M["workloads"]}
    for cell in entry["workloads"]:
        conf = json.loads((BENCH / "configs" / f"{cells[cell]}.json").read_text())
        assert conf["model_config"]["num_experts"] > 0
    assert any(m["layer"] == "kernels" and m is not entry
               for m in M["per_layer"])
