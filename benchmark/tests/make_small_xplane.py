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

if __name__ == "__main__":
    out = Path(__file__).parent / "data" / "small.xplane.pb"
    out.write_bytes(ProfileData.text_proto_to_serialized_xspace(TEXT))
    print(out, out.stat().st_size, "bytes")
