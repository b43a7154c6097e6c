"""How tier-1's files are dealt over the driver's workers (``docs/DESIGN.md``,
"How tier-1 is dealt"): the order ``tests/conftest.py`` sorts the collected
items into, the record it sorts by, and ``tools/tier1_deal.py``, which makes
that record and replays the deal over a junit's seconds.
"""

import types
from pathlib import Path

import conftest
from test_yardstick import load_by_path

TESTS = Path(__file__).resolve().parent


def dealt(names, record, monkeypatch):
    """``names`` (a file's name a collected case) as the conftest sorts
    them under ``record``."""
    monkeypatch.setattr(conftest, "TIER1_SECONDS", record)
    items = [types.SimpleNamespace(path=TESTS / name, case=i)
             for i, name in enumerate(names)]
    conftest.pytest_collection_modifyitems(items)
    return [(i.path.name, i.case) for i in items]


def test_an_unknown_file_first_then_longest_first_and_a_file_s_own_order(
        monkeypatch):
    names = ["test_light.py", "test_heavy.py", "test_light.py", "test_new.py",
             "test_heavy.py", "test_new.py"]
    record = {"test_light.py": 0.5, "test_heavy.py": 600.0}
    assert dealt(names, record, monkeypatch) == [
        ("test_new.py", 3), ("test_new.py", 5), ("test_heavy.py", 1),
        ("test_heavy.py", 4), ("test_light.py", 0), ("test_light.py", 2)]
    # an empty record keeps the collection's order: it gates nothing
    assert dealt(names, {}, monkeypatch) == list(zip(names, range(6)))


def test_the_tool_replays_the_deal_over_a_junit_s_seconds(tmp_path, capsys,
                                                          monkeypatch):
    tool = load_by_path(TESTS.parent / "tools" / "tier1_deal.py")
    cases = [("a", "one", 300), ("a", "two", 200), ("b", "one", 450),
             ("c", "one", 61), ("c", "two", 39), ("d", "one", 250),
             ("new", "one", 50)]
    junit = tmp_path / "t1.xml"
    junit.write_text(
        '<testsuites><testsuite name="pytest" tests="7" time="777.2">'
        + "".join(f'<testcase classname="tests.test_{f}" name="test_{n}" '
                  f'time="{s}.0" />' for f, n, s in cases)
        + "</testsuite></testsuites>")
    files, listed, wall = tool.read_junit(junit)
    assert files == {"test_a.py": 500, "test_b.py": 450, "test_c.py": 100,
                     "test_d.py": 250, "test_new.py": 50}
    assert wall == 777.2 and len(listed) == 7
    record = {"test_a.py": 500, "test_b.py": 450, "test_c.py": 100,
              "test_d.py": 250}
    # two workers: new 50 | a 500 -> new+b 500 | a 500 -> d 750 | 500
    # -> d 750 | a+c 600
    assert tool.dealt_wall(files, record, 2) == 750
    # (the order is the conftest's for the same record)
    assert [name for name, _ in dealt(sorted(files), record, monkeypatch)] == [
        "test_new.py", "test_a.py", "test_b.py", "test_d.py", "test_c.py"]
    # three workers: new 50 | a 500 | b 450 -> new+d 300 -> new+d+c 400
    assert tool.dealt_wall(files, record, 3) == 500
    # no record, the collection's order: a 500 | b 450 -> b+c 550
    # -> a+d 750 -> b+c+new 600
    assert tool.dealt_wall(files, {}, 2) == 750
    kept = tmp_path / "seconds.json"
    monkeypatch.setattr(tool, "RECORD", kept)
    tool.main([str(junit), "-n", "2", "--write"])
    assert tool.json.loads(kept.read_text()) == files
    out = capsys.readouterr().out
    assert "case seconds 1350 in 7 cases of 5 files" in out
    assert "even share over 2: 675" in out
    assert "wall as dealt by the record: 700" in out     # new is known now
    assert "wall of this run: 777" in out
    assert "file over 400 s: test_a.py 500" in out
    assert "file over 400 s: test_b.py 450" in out
    assert "case over 60 s: test_c.py::test_one 61" in out
    assert "test_c.py::test_two" not in out


def test_every_name_of_the_record_is_a_file_of_tests():
    """A renamed file drops out of the record (and is dealt first until
    ``tools/tier1_deal.py --write`` knows it) instead of rotting in it."""
    assert [n for n in conftest.TIER1_SECONDS
            if not (TESTS / n).is_file()] == []
