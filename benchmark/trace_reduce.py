#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to numbers.

    python3 benchmark/trace_reduce.py <trace.xplane.pb> <out.json>
    python3 benchmark/trace_reduce.py --inspect <trace.xplane.pb>

Runs in a process of its own (it imports ``jax.profiler`` to read the
file, and the benchmark's parent never imports JAX).  What a v5e trace
holds today (looked at by hand, PR 22; see PERF.md section 3):

* one plane ``/device:TPU:<n>`` per chip.  Its line ``XLA Ops`` has one
  event per executed HLO op, named by the op's whole HLO line
  (``%fusion.382 = bf16[...] fusion(...)``; kept here up to the `` = ``)
  and nested (a ``while`` covers its body's ops).  Its line ``XLA
  Modules`` has one event per executed program, ``jit_<function>(<fingerprint>)``:
  both variants of the serving step are ``jit_mixed_step``.  Its line
  ``Async XLA Ops`` (copy-start .. copy-done spans) overlaps the ops and
  is not counted;
* the paged attention kernels are the custom calls ``_paged_call.<n>``
  (decode) and ``_paged_prefill_call.<n>``;
* the other planes (``/host:CPU`` with the runtime's own spans,
  ``#Chip0 ...``, ``/host:metadata``, ``/device:CUSTOM:...``) are not the
  device.  All instants count nanoseconds from the profiler's start.

Busy time is the union of the op intervals of a chip; idle is the rest of
the window.  An op's own time is its interval minus the ops nested in it.
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")


def load(path: str) -> list:
    """``[{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns), ...]}]}]`` of every plane in the file."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (short_name(ev.name), float(ev.start_ns),
                 float(ev.duration_ns)) for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the trace
    prints an op as its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events) -> dict:
    """Own time by op name: each event's duration minus the events
    nested directly inside it (a ``while`` minus its body)."""
    own = defaultdict(float)
    stack = []                                   # [end, name]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= dur
        own[name] += dur
        stack.append([start + dur, name])
    return own


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(planes: list) -> dict:
    """The numbers the layer metrics read.  Times in seconds unless the
    key says otherwise; instants are the trace's own nanoseconds (from
    the profiler's start)."""
    chips = sorted((int(DEVICE_PLANE.match(p["name"]).group(1)), p)
                   for p in planes if DEVICE_PLANE.match(p["name"])
                   and _line(p, OPS_LINE))
    if not chips:
        return {}
    starts = [e[1] for _, p in chips for e in _line(p, OPS_LINE)]
    ends = [e[1] + e[2] for _, p in chips for e in _line(p, OPS_LINE)]
    w0, w1 = min(starts), max(ends)
    busy, gaps_of = [], {}
    own_total = defaultdict(float)
    for cid, p in chips:
        ops = _line(p, OPS_LINE)
        merged = union([s, s + d] for _, s, d in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        gaps_of[cid] = [[merged[i][1], merged[i + 1][0] - merged[i][1]]
                        for i in range(len(merged) - 1)]
        for name, t in self_times(ops).items():
            own_total[name] += t / 1e9 / len(chips)
    worst_chip = chips[min(range(len(chips)), key=lambda i: busy[i])][0]
    window_s = (w1 - w0) / 1e9
    ranked = sorted(own_total.items(), key=lambda kv: -kv[1])
    modules = defaultdict(list)
    for name, start, dur in _line(chips[0][1], MODULES_LINE):
        modules[MODULE_NAME.match(name).group(1)].append([start, dur])
    busy_total = sum(own_total.values())
    gaps = sorted(gaps_of[worst_chip], key=lambda g: -g[1])
    return {
        "chips": len(chips),
        "window_ns": [w0, w1],
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_chip": busy,
        "idle_pct_worst": 100.0 * (1.0 - min(busy) / window_s),
        "op_self_s": ranked,
        "op_self_total_s": busy_total,
        "modules": dict(modules),
        "longest_gaps": [[s, d / 1e9] for s, d in gaps[:5]],
        "gap_count": len(gaps),
    }


def inspect(planes: list, top: int = 25) -> str:
    """A page of text about a trace: planes, lines, event counts and the
    names that take most time.  For looking at a trace by hand."""
    out = []
    for p in planes:
        out.append(f"plane {p['name']!r}")
        for line in p["lines"]:
            evs = line["events"]
            if not evs:
                continue
            t0 = min(e[1] for e in evs)
            t1 = max(e[1] + e[2] for e in evs)
            out.append(f"  line {line['name']!r}: {len(evs)} events, "
                       f"{t0:.0f} .. {t1:.0f} ns "
                       f"({(t1 - t0) / 1e9:.3f} s)")
            by = defaultdict(lambda: [0, 0.0])
            for name, _, d in evs:
                by[name][0] += 1
                by[name][1] += d
            for name, (n, d) in sorted(by.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                out.append(f"      {d / 1e6:12.3f} ms  x{n:<7} {name[:110]}")
    return "\n".join(out)


def main(argv) -> int:
    if len(argv) == 2 and argv[0] == "--inspect":
        print(inspect(load(argv[1])))
        return 0
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    planes = load(argv[0])
    with open(argv[1], "w") as f:
        json.dump(reduce(planes), f)
    with open(argv[1] + ".inspect.txt", "w") as f:
        f.write(inspect(planes))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
