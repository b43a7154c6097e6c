#!/usr/bin/env python3
"""Writes ``data/small.xplane.pb``: a two-chip trace small enough to work
by hand, in the layout a v5e trace has (PERF.md section 3).  Run once; the
file is kept in git so the test needs no protobuf tooling beyond JAX's.

Chip 0, line "XLA Ops" (times in microseconds from the line's start):
    while.1      0 .. 100   (covers the two below)
      fusion.1  10 ..  40
      _paged_call.9 50 .. 90
    copy.58    150 .. 200
    fusion.1   200 .. 230   (touches copy.58: no gap)
    all-reduce.3 400 .. 450
  busy = 100 + 80 + 50 = 230 us of a 450 us window; gaps 50 us and 170 us.
Chip 1: one op fusion.1 0 .. 450 (busy all the window).
Line "XLA Modules" on chip 0: jit_mixed_step(123) 0 .. 230 and 400 .. 450.
A host plane carries an event that must be ignored.

``write_generated(path, ...)`` writes a trace of any size in the same
layout for the tests of what reading costs (nothing of it is kept in git):
device planes whose ops have long HLO lines and per-event stats, an
``Async XLA Ops`` line, and a host plane with many more events than the
device planes.  It writes the wire format by hand, so the tests of the
reader do not lean on the reader's own schema.
"""
from pathlib import Path

from jax.profiler import ProfileData

NAMES = {1: "while.1", 2: "fusion.1", 3: "%_paged_call.9 = bf16[2] custom-call()", 4: "copy.58",
         5: "all-reduce.3", 6: "jit_mixed_step(123)", 7: "host_thing"}


def events(rows):
    return "".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * 1e6)} "
        f"duration_ps: {int((e - s) * 1e6)} }} " for m, s, e in rows)


def plane(pid, name, lines):
    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }} '
                   for k, v in NAMES.items())
    body = "".join(
        f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 1000000 '
        f'{events(rows)} }} ' for i, (ln, rows) in enumerate(lines))
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} }} '


TEXT = (
    plane(1, "/device:TPU:0", [
        ("XLA Ops", [(1, 0, 100), (2, 10, 40), (3, 50, 90), (4, 150, 200),
                     (2, 200, 230), (5, 400, 450)]),
        ("XLA Modules", [(6, 0, 230), (6, 400, 450)])])
    + plane(2, "/device:TPU:1", [("XLA Ops", [(2, 0, 450)])])
    + plane(3, "/host:CPU", [("python", [(7, 0, 1000)])]))



def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _sub(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _event(metadata_id: int, offset_ps: int, duration_ps: int,
           stats: int = 0) -> bytes:
    """An XEvent with ``stats`` XStats (metadata id, uint64 value)."""
    body = _int(1, metadata_id) + _int(2, offset_ps) + _int(3, duration_ps)
    for i in range(stats):
        body += _sub(4, _int(1, i + 1) + _int(3, offset_ps + i))
    return body


def _line(name: str, timestamp_ns: int, events: list) -> bytes:
    return (_sub(2, name.encode()) + _int(3, timestamp_ns)
            + b"".join(_sub(4, e) for e in events))


def _plane(pid: int, name: str, lines: list, names: dict) -> bytes:
    body = _int(1, pid) + _sub(2, name.encode())
    for ln in lines:
        body += _sub(3, ln)
    for key, text in names.items():
        meta = (_int(1, key) + _sub(2, text.encode())
                + _sub(5, _int(1, 1) + _sub(5, b"jit(f)/scope/op")))
        body += _sub(4, _int(1, key) + _sub(2, meta))
    return body


def write_generated(path, chips: int = 2, ops: int = 50,
                    executions: int = 20, name_len: int = 1500,
                    host_events: int = 20000) -> dict:
    """Write such a trace to ``path``; returns what it holds:
    ``device_events`` (both read lines, all chips), ``distinct_ops``
    (entries of a device plane's event_metadata), ``host_events``.  Each
    execution is one module event over a ``while`` that nests ``ops - 1``
    ops of 3,001,500 ps every 4,000,700 ps (so instants and durations are
    not whole nanoseconds), then a gap."""
    names = {1: "jit_mixed_step(77)",
             2: "%while.1 = (s32[]) while(%tuple), body=%b"}
    for k in range(3, ops + 2):
        names[k] = (f"%fusion.{k} = bf16[32,4,8,128]{{3,2,1,0}} fusion("
                    + "bf16[8,128]{1,0} %p, " * (name_len // 22) + ")")
    step_ps = 4_000_700
    exec_ps = step_ps * ops
    planes = []
    for chip in range(chips):
        op_events, module_events, async_events = [], [], []
        for x in range(executions):
            t = x * (exec_ps + 9_000_300)
            module_events.append(_event(1, t, exec_ps, 2))
            op_events.append(_event(2, t, exec_ps - 500, 3))
            for k in range(3, ops + 2):
                op_events.append(_event(k, t + (k - 3) * step_ps + 200,
                                        3_001_500, 3))
            async_events.append(_event(3, t, 7_000_000, 3))
        planes.append(_plane(chip + 1, f"/device:TPU:{chip}", [
            _line("XLA Modules", 5_000_000, module_events),
            _line("XLA Ops", 5_000_000, op_events),
            _line("Async XLA Ops", 5_000_000, async_events),
            _line("Steps", 5_000_000, module_events)], names))
    host_names = {k: f"host_span_{k}" for k in range(1, 40)}
    per_line = host_events // 4
    planes.append(_plane(chips + 1, "/host:CPU", [
        _line(f"thread/{i}", 4_000_000,
              [_event(1 + j % 39, j * 1000, 900, 2) for j in range(per_line)])
        for i in range(4)], host_names))
    planes.append(_plane(chips + 2, "/device:CUSTOM:Megascale", [
        _line("XLA Ops", 1, [_event(1, 0, 10)])], {1: "not_a_tpu_op"}))
    with open(path, "wb") as f:
        f.write(b"".join(_sub(1, p) for p in planes))
    return {"device_events": chips * executions * (ops + 1),
            "distinct_ops": len(names), "host_events": per_line * 4,
            "executions": executions, "chips": chips}


if __name__ == "__main__":
    out = Path(__file__).parent / "data" / "small.xplane.pb"
    out.write_bytes(ProfileData.text_proto_to_serialized_xspace(TEXT))
    print(out, out.stat().st_size, "bytes")
