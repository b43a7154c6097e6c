"""BENCHMARK.json against the files it names, and run.py against names."""
import importlib
import json
import re
from pathlib import Path

import pytest

import run as bench_run

BENCH = Path(__file__).resolve().parent.parent
M = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_named_file_exists():
    for c in M["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    for w in M["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "cells" / f"{w['name']}.json").is_file()
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "generators" / f"{mix['generator']}.py").is_file()
    for m in M["end_to_end"]:
        assert (BENCH / "end_to_end" / f"{m['name']}.py").is_file()
    for m in M["per_layer"]:
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()


def test_names_units_and_limits_of_the_contract():
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[g]]
    assert all(NAME.match(n) for n in names)
    for g in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in M[g]}) == len(M[g])
    assert all(len(w["why"]) <= 200 for w in M["workloads"] + M["configs"])
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.1
                                    for m in e2e.values())
    assert all(m["moves"] in e2e for m in M["per_layer"])
    assert all(re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
               for m in M["end_to_end"] + M["per_layer"])
    rs = M["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configuration_files_agree_with_themselves():
    for c in M["configs"]:
        conf = json.loads((BENCH.parent / c["file"]).read_text())
        mc = conf["model_config"]
        assert conf["hidden_size"] == mc["hidden_size"]
        assert conf["vocab_size"] == mc["vocab_size"]
        assert conf.get("num_hidden_layers", conf.get("n_layer")) == mc["num_layers"]
        assert conf["reduced"] == c["reduced"] and conf["source"] == c["source"]
        blocks = int(conf["serve_flags"][conf["serve_flags"].index(
            "--kv-cache-blocks") + 1])
        assert blocks == conf["pool"]["blocks"]


def test_run_py_holds_no_cell_configuration_mix_or_metric_name():
    text = (BENCH / "run.py").read_text()
    names = {x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[g]}
    names |= {w["traffic"] for w in M["workloads"]}
    names |= {p.stem for p in (BENCH / "generators").glob("*.py")
              if p.stem not in ("__init__", "common")}
    assert not [n for n in names if n in text]


def test_a_layer_metric_is_listed_only_where_the_metric_it_moves_is():
    e2e = {m["name"]: m.get("workloads") for m in M["end_to_end"]}
    cells = [w["name"] for w in M["workloads"]]
    for m in M["per_layer"]:
        for cell in m.get("workloads", cells):
            assert e2e[m["moves"]] is None or cell in e2e[m["moves"]], m["name"]
    for cell in cells:            # every cell: setup_s and one more, and a layer metric
        have = [n for n, w in e2e.items() if w is None or cell in w]
        assert "setup_s" in have and len(have) >= 2
        assert any(cell in m.get("workloads", cells) for m in M["per_layer"])


class _NeedsTheRun(Exception):
    """A reader asked for something only a run has."""


class _CellAlone(dict):
    """A reader's context before any run: the cell, its mix and its load."""

    def __missing__(self, key):
        raise _NeedsTheRun(key)


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_a_cell_lists_no_metric_whose_reader_refuses_its_kind_of_loop(cell):
    """A reader that returns ``None`` on the mix alone (``tpot_p90_ms`` and
    the client's TTFT statistics in a closed loop) has nothing to read in
    that cell whatever the run: the cell may not list it."""
    w = next(w for w in M["workloads"] if w["name"] == cell)
    ctx = _CellAlone(
        cell=w, mix=json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        load=json.loads((BENCH / "cells" / f"{cell}.json").read_text()))
    listed = [(package, m["name"])
              for package, group in (("end_to_end", "end_to_end"),
                                     ("layer_metrics", "per_layer"))
              for m in bench_run.metric_entries(M, group, cell)]
    assert ("end_to_end", "tpot_p50_ms") in listed
    for package, name in listed:
        read = importlib.import_module(f"{package}.{name}").read
        try:
            assert read(ctx) is not None, f"{cell} lists {name}"
        except _NeedsTheRun:
            pass
