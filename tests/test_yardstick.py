"""Tier-1's hold on the yardstick (``benchmark/``), which the driver's
test command does not collect.

- ``benchmark/trace_reduce.py`` is the one reader every per-layer device
  metric goes through.  It is loaded here by its path (it imports nothing
  of the benchmark) and reduces the small recorded trace through both of
  its routes; the numbers are the ones ``benchmark/tests/make_small_xplane.py``
  built the file to have.
- ``benchmark/tests`` runs here, a case and a subprocess a file of it (it
  stood at 70 of 75 over three PRs and no command of the driver's showed
  it): the files found by glob, so a new one is a case the day it lands.
  The ``test_*_family.py`` files, most of the seconds, are the cases of
  ``tests/test_yardstick_families.py``, so that neither file is a unit
  the deal cannot place (``docs/DESIGN.md``, "How tier-1 is dealt").
"""

import importlib.util
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
SMALL_TRACE = BENCH / "tests" / "data" / "small.xplane.pb"


def load_by_path(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("route", ["proto", "profile_data"])
def test_trace_reducer_reads_the_small_trace(route):
    trace_reduce = load_by_path(BENCH / "trace_reduce.py")
    assert route in trace_reduce.ROUTES
    planes, taken = trace_reduce.load(str(SMALL_TRACE), route)
    assert taken == route
    # the file holds two chips and a host plane whose event is 1,000 us long
    assert [p["name"] for p in planes] == [
        "/device:TPU:0", "/device:TPU:1", "/host:CPU"]
    reduced = trace_reduce.reduce(planes)
    assert reduced["chips"] == 2
    assert reduced["window_s"] == pytest.approx(450e-6)
    # chip 0 is busy 230 of 450 us (the union of its ops), chip 1 all of it
    assert reduced["busy_s_per_chip"] == pytest.approx([230e-6, 450e-6])
    assert reduced["idle_pct_worst"] == pytest.approx(100 * (1 - 230 / 450))
    # two executions of the serving step on the ``XLA Modules`` line
    assert list(reduced["modules"]) == ["jit_mixed_step"]
    assert len(reduced["modules"]["jit_mixed_step"]) == 2


def benchmark_test_files(family: bool):
    return sorted(p.name for p in (BENCH / "tests").glob("test_*.py")
                  if p.name.endswith("_family.py") == family)


def run_benchmark_test_file(name, *options, case=None):
    """``options`` are further words for pytest; ``case`` is one test of
    the file, run alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(Path(__file__).resolve().parent),
                    *filter(None, [os.environ.get("PYTHONPATH")])]))
    node = str(BENCH / "tests" / name) + (f"::{case}" if case else "")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", node, "-q",
         "-p", "no:cacheprovider", *options],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        # the longest (xing's: a rehearsal of its cell) takes 150 s alone
        # and 210 s beside five busy workers on eight cores
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-1000:]
    assert re.search(r"\b[1-9]\d* passed", proc.stdout), proc.stdout[-1000:]


@pytest.mark.parametrize("name", benchmark_test_files(family=False))
def test_a_file_of_the_benchmarks_own_suite_passes(name):
    run_benchmark_test_file(name)
