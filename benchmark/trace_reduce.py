#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to numbers.

    python3 benchmark/trace_reduce.py <trace.xplane.pb> <out.json>
    python3 benchmark/trace_reduce.py --inspect <trace.xplane.pb>

Runs in a process of its own (the benchmark's parent never imports JAX).
What a v5e trace holds (looked at by hand, PR 22 and PR 30; see PERF.md
section 3):

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

Only the two lines above are read.  What reading costs must not grow with
what is not read, and as little as may be with what is (PR 30: a faster
program puts more executions into the traced seconds), so:

* the file is read as the ``XSpace`` message itself, through a descriptor
  of the few fields used, built here from ``google.protobuf`` alone (no
  generated module, no ``import tensorflow``).  Planes, lines and, in the
  lines that are not read, events are taken as undecoded bytes: a plane
  or line that is not read costs its copy and a count;
* an op's short name is cut once per entry of the plane's
  ``event_metadata`` and an event looks it up by id;
* the page of text about the trace (``inspect``) is built from counts
  gathered in that same pass.

Where ``google.protobuf`` cannot be imported the file is read through
``jax.profiler.ProfileData`` (slower: it builds each event's long name as
a new string; lines that are not read are then not counted either), and
stderr says so.  Both routes give the same numbers: an instant is the
line's ``timestamp_ns`` plus the whole nanoseconds of the event's
picosecond offset, a duration the whole nanoseconds of its picoseconds,
as ``ProfileData`` hands them out.

Busy time is the union of the op intervals of a chip; idle is the rest of
the window.  An op's own time is its interval minus the ops nested in it.
"""

from __future__ import annotations

import functools
import json
import re
import sys
import time
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
READ_LINES = (OPS_LINE, MODULES_LINE)
MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
ROUTES = ("proto", "profile_data")


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the trace
    prints an op as its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_read(plane_name: str, line_name: str) -> bool:
    return bool(DEVICE_PLANE.match(plane_name)) and line_name in READ_LINES


def _new_line(name: str, count) -> dict:
    return {"name": name, "count": count, "events": [], "by_name": {},
            "span": None}


def _gather(line: dict, triples) -> None:
    """Fill a line that is read from ``(name, start_ns, dur_ns)`` triples:
    its events and, in the same pass, what ``inspect`` prints of it
    (count and duration by name, first start and last end)."""
    events, by = line["events"], line["by_name"]
    t0, t1 = float("inf"), float("-inf")
    for ev in triples:
        events.append(ev)
        name, s, d = ev
        if s < t0:
            t0 = s
        if s + d > t1:
            t1 = s + d
        got = by.get(name)
        if got is None:
            by[name] = [1, d]
        else:
            got[0] += 1
            got[1] += d
    line["count"], line["span"] = len(events), (t0, t1)


# --- the proto route --------------------------------------------------

# (message, [(field, number, type, repeated)]): the fields of
# tsl/profiler/protobuf/xplane.proto that are used, under their own
# numbers.  ``bytes`` where the proto has a message that is decoded later
# or never; a map is a repeated entry of key 1 and value 2.
_SCHEMA = (
    ("Space", [("planes", 1, "bytes", True)]),
    ("Meta", [("name", 2, "string", False)]),
    ("MetaEntry", [("key", 1, "int64", False), ("value", 2, "Meta", False)]),
    ("Plane", [("name", 2, "string", False), ("lines", 3, "bytes", True),
               ("event_metadata", 4, "MetaEntry", True)]),
    ("LineHead", [("name", 2, "string", False),
                  ("events", 4, "bytes", True)]),
    ("Event", [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)]),
    ("Line", [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "Event", True)]),
)


@functools.lru_cache(maxsize=None)
def _messages() -> dict:
    """The message classes of ``_SCHEMA``, built once."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    scalar = {"int64": 3, "string": 9, "bytes": 12}
    package = "benchmark_xplane_subset"
    fdp = descriptor_pb2.FileDescriptorProto(
        name=f"{package}.proto", package=package, syntax="proto3")
    for msg_name, fields in _SCHEMA:
        msg = fdp.message_type.add(name=msg_name)
        for name, number, kind, repeated in fields:
            f = msg.field.add(name=name, number=number,
                              label=3 if repeated else 1)
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = 11, f".{package}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {msg_name: message_factory.GetMessageClass(
                pool.FindMessageTypeByName(f"{package}.{msg_name}"))
            for msg_name, _ in _SCHEMA}


def _decode_line(raw: bytes):
    """One line that is read, events and all."""
    return _messages()["Line"].FromString(raw)


def _load_proto(path: str) -> list:
    m = _messages()
    with open(path, "rb") as f:
        space = m["Space"].FromString(f.read())
    planes = []
    for raw_plane in space.planes:
        plane = m["Plane"].FromString(raw_plane)
        names = ({e.key: short_name(e.value.name)
                  for e in plane.event_metadata}
                 if DEVICE_PLANE.match(plane.name) else {})
        lines = []
        for raw_line in plane.lines:
            head = m["LineHead"].FromString(raw_line)
            line = _new_line(head.name, len(head.events))
            if is_read(plane.name, head.name):
                full = _decode_line(raw_line)
                ts = full.timestamp_ns
                _gather(line, (
                    (names[ev.metadata_id],
                     float(ts + ev.offset_ps // 1000),
                     float(ev.duration_ps // 1000)) for ev in full.events))
            lines.append(line)
        planes.append({"name": plane.name, "lines": lines})
    return planes


# --- the fallback -----------------------------------------------------

def _cut(cut: dict, long: str) -> str:
    name = cut.get(long)
    if name is None:
        name = cut[long] = short_name(long)
    return name


def _load_profile_data(path: str) -> list:
    from jax.profiler import ProfileData
    cut = {}                    # long name -> short: no id is shown here
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for pline in plane.lines:
            line = _new_line(pline.name, None)
            if is_read(plane.name, pline.name):
                _gather(line, ((_cut(cut, ev.name), float(ev.start_ns),
                                float(ev.duration_ns))
                               for ev in pline.events))
            lines.append(line)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load(path: str, route: str | None = None) -> tuple:
    """``(planes, route taken)``.  ``planes`` is ``[{"name", "lines":
    [{"name", "count", "events": [(name, start_ns, dur_ns), ...]}]}]`` of
    every plane in the file (and per line ``by_name`` and ``span``, for
    ``inspect``); ``events`` is filled for the two lines of a device
    plane that are read and empty elsewhere, ``count`` is the line's
    number of events (None: not counted, the fallback's lines that are
    not read).  ``route`` forces one of ``ROUTES``."""
    if route in (None, "proto"):
        try:
            _messages()
        except ImportError as e:
            if route == "proto":
                raise
            print(f"trace_reduce: google.protobuf cannot be imported ({e}); "
                  "falling back to jax.profiler.ProfileData (slower; lines "
                  "that are not read are not counted)", file=sys.stderr)
            route = "profile_data"
        else:
            route = "proto"
    if route == "proto":
        return _load_proto(path), route
    return _load_profile_data(path), route


def counts(planes: list) -> dict:
    """Events read and, by plane, events skipped (with the plane's
    largest skipped lines)."""
    read, skipped = 0, {}
    for p in planes:
        rest = [(ln["name"], ln["count"]) for ln in p["lines"]
                if not is_read(p["name"], ln["name"])]
        read += sum(len(ln["events"]) for ln in p["lines"])
        if rest:
            counted = [(n, c) for n, c in rest if c is not None]
            skipped[p["name"]] = {
                "lines": len(rest),
                "events": sum(c for _, c in counted) if counted else None,
                "largest": sorted(counted, key=lambda nc: -nc[1])[:5]}
    return {"events_read": read, "skipped": skipped}


def by_start(events) -> list:
    """Events in the order both passes below walk them: by start, the
    longer first where two start together (a ``while`` before its body)."""
    return sorted(events, key=lambda e: (e[1], -e[2]))


def union(intervals, ordered: bool = False) -> list:
    """Sorted, merged ``[start, end]`` intervals (``ordered``: they come
    by start already)."""
    out = []
    for s, e in intervals if ordered else sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events, ordered: bool = False) -> dict:
    """Own time by op name: each event's duration minus the events
    nested directly inside it (a ``while`` minus its body).  ``ordered``:
    the events come from ``by_start`` already."""
    own = defaultdict(float)
    stack = []                                   # [end, name]
    for name, start, dur in events if ordered else by_start(events):
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
    busy, gaps_of, firsts, lasts = [], {}, [], []
    own_total = defaultdict(float)
    for cid, p in chips:
        ops = by_start(_line(p, OPS_LINE))      # sorted once for both
        merged = union(([s, s + d] for _, s, d in ops), ordered=True)
        firsts.append(merged[0][0])
        lasts.append(merged[-1][1])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        gaps_of[cid] = [[merged[i][1], merged[i + 1][0] - merged[i][1]]
                        for i in range(len(merged) - 1)]
        for name, t in self_times(ops, ordered=True).items():
            own_total[name] += t / 1e9 / len(chips)
    w0, w1 = min(firsts), max(lasts)
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
    """A page of text about a trace: every plane and line with its event
    count and, for the lines that are read, their span and the names that
    take most time (gathered while the line was read).  For looking at a
    trace by hand."""
    out = []
    for p in planes:
        out.append(f"plane {p['name']!r}")
        for line in p["lines"]:
            if not line["events"]:
                if line["count"] != 0:
                    n = "?" if line["count"] is None else line["count"]
                    out.append(f"  line {line['name']!r}: {n} events, "
                               "not read")
                continue
            t0, t1 = line["span"]
            out.append(f"  line {line['name']!r}: {line['count']} events, "
                       f"{t0:.0f} .. {t1:.0f} ns "
                       f"({(t1 - t0) / 1e9:.3f} s)")
            for name, (n, d) in sorted(line["by_name"].items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                out.append(f"      {d / 1e6:12.3f} ms  x{n:<7} {name[:110]}")
    return "\n".join(out)


def main(argv) -> int:
    if len(argv) == 2 and argv[0] == "--inspect":
        print(inspect(load(argv[1])[0]))
        return 0
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    t0 = time.monotonic()
    planes, route = load(argv[0])
    t1 = time.monotonic()
    reduced = reduce(planes)
    t2 = time.monotonic()
    text = inspect(planes)
    t3 = time.monotonic()
    # read by no metric: what this reduction cost (run.py's [time] line)
    reduced["reducer"] = {"route": route, "load_s": t1 - t0,
                          "reduce_s": t2 - t1, "inspect_s": t3 - t2,
                          **counts(planes)}
    with open(argv[1], "w") as f:
        json.dump(reduced, f)
    with open(argv[1] + ".inspect.txt", "w") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
