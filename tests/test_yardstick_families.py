"""The families' files of ``benchmark/tests`` (``test_*_family.py``: a
configuration's model against its plain reference), a case and a
subprocess each: see ``tests/test_yardstick.py``, which holds the others.
"""

import pytest

from test_yardstick import benchmark_test_files, run_benchmark_test_file


@pytest.mark.parametrize("name", benchmark_test_files(family=True))
def test_a_family_s_file_of_the_benchmarks_own_suite_passes(name):
    run_benchmark_test_file(name)
