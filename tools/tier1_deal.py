#!/usr/bin/env python3
"""Where tier-1's seconds are, and the wall its deal gives.

    python tools/tier1_deal.py /tmp/_t1.xml [-n 6] [--write]

Reads the junit of the driver's command (``docs/DESIGN.md``, "How tier-1
is dealt") and prints the case seconds in all and by file, the even share
over ``-n`` workers, the wall that list scheduling gives when the files
are dealt in the order ``tests/conftest.py`` sorts them into by
``tests/data/tier1_seconds.json`` (a file the record does not know first,
then longest first; each next file to the worker with the least seconds),
the files over 400 s and the cases over 60 s.  ``--write`` makes the
record anew from this junit.
"""

import argparse
import heapq
import json
import math
import xml.etree.ElementTree as ET
from pathlib import Path

RECORD = Path(__file__).resolve().parent.parent / "tests/data/tier1_seconds.json"
FILE_LIMIT_S, CASE_LIMIT_S = 400, 60


def read_junit(path):
    """``({file name: seconds}, [(seconds, case id)], the run's own wall)``."""
    suite = next(ET.parse(path).getroot().iter("testsuite"))
    files, cases = {}, []
    for case in suite.iter("testcase"):
        name = case.get("classname").split(".")[1] + ".py"
        seconds = float(case.get("time"))
        files[name] = files.get(name, 0.0) + seconds
        cases.append((seconds, f"{name}::{case.get('name')}"))
    return files, cases, float(suite.get("time"))


def dealt_wall(files, record, workers):
    """List scheduling of ``files`` in the order the record gives them."""
    order = sorted(sorted(files), key=lambda f: -record.get(f, math.inf))
    loads = [0.0] * workers
    for name in order:
        heapq.heapreplace(loads, loads[0] + files[name])
    return max(loads)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("junit")
    ap.add_argument("-n", type=int, default=6, help="workers (the driver's 6)")
    ap.add_argument("--write", action="store_true",
                    help=f"refresh {RECORD.name} from this junit")
    args = ap.parse_args(argv)
    files, cases, wall = read_junit(args.junit)
    if args.write:
        RECORD.write_text(json.dumps(
            {f: round(s, 1) for f, s in sorted(files.items())}, indent=0) + "\n")
    record = json.loads(RECORD.read_text())
    total = sum(files.values())
    for name, s in sorted(files.items(), key=lambda kv: -kv[1]):
        print(f"{s:8.1f}  {name}" + ("" if name in record else "  (not in the record)"))
    print(f"case seconds {total:.0f} in {len(cases)} cases of {len(files)} files")
    print(f"even share over {args.n}: {total / args.n:.0f}")
    print(f"wall as dealt by the record: {dealt_wall(files, record, args.n):.0f}")
    print(f"wall of this run: {wall:.0f}")
    for name, s in sorted(files.items()):
        if s > FILE_LIMIT_S:
            print(f"file over {FILE_LIMIT_S} s: {name} {s:.0f}")
    for s, case in sorted(cases, reverse=True):
        if s > CASE_LIMIT_S:
            print(f"case over {CASE_LIMIT_S} s: {case} {s:.0f}")


if __name__ == "__main__":
    main()
