"""Writes ``two_ops.xplane.pb``: a hand-built profiler trace with one
device plane (two operations with a gap between them inside one program,
then a third after a second gap), and one host thread with two
``engine_step`` spans, in the wire format of XSpace (tsl/profiler
``xplane.proto``) encoded by hand so that no protobuf package is needed.

    python3 benchmark/fixtures/make_trace.py

The expected reduction is in ``two_ops.expected.json`` and is derived by
hand from the numbers below, not by running the reduction.
"""

from __future__ import annotations

from pathlib import Path


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(num: int, wire: int, payload: bytes) -> bytes:
    return _varint(num << 3 | wire) + payload


def _int(num: int, v: int) -> bytes:
    return _field(num, 0, _varint(v))


def _msg(num: int, body: bytes) -> bytes:
    return _field(num, 2, _varint(len(body)) + body)


def _str(num: int, s: str) -> bytes:
    return _msg(num, s.encode())


def event(metadata_id: int, offset_ps: int, duration_ps: int) -> bytes:
    return _int(1, metadata_id) + _int(2, offset_ps) + _int(3, duration_ps)


def line(line_id: int, name: str, timestamp_ns: int, events) -> bytes:
    body = _int(1, line_id) + _str(2, name) + _int(3, timestamp_ns)
    for ev in events:
        body += _msg(4, ev)
    return body


def plane(plane_id: int, name: str, lines, metadata) -> bytes:
    body = _int(1, plane_id) + _str(2, name)
    for ln in lines:
        body += _msg(3, ln)
    for mid, mname in metadata.items():
        entry = _int(1, mid) + _msg(2, _int(1, mid) + _str(2, mname))
        body += _msg(4, entry)
    return body


US = 1_000_000      # picoseconds in a microsecond
T0_NS = 1_000_000   # the lines' common start, in nanoseconds

# device: [0,100us) while.1 containing fusion.1 [10,40) and
# custom-call.2 [60,90); then fusion.3 [150,200) and fusion.4 [300,310).
# Programs: jit_step [0,100), jit_other [150,200) and [300,310).
DEVICE = plane(1, "/device:TPU:0", [
    line(1, "XLA Ops", T0_NS, [
        event(1, 0 * US, 100 * US),
        event(2, 10 * US, 30 * US),
        event(3, 60 * US, 30 * US),
        event(4, 150 * US, 50 * US),
        event(7, 300 * US, 10 * US),
    ]),
    line(2, "XLA Modules", T0_NS, [
        event(5, 0 * US, 100 * US),
        event(6, 150 * US, 50 * US),
        event(6, 300 * US, 10 * US),
    ]),
], {1: "while.1", 2: "fusion.1", 3: "custom-call.2", 4: "fusion.3",
    5: "jit_step(123)", 6: "jit_other(456)", 7: "fusion.4"})

# host: engine_step [0,130us) and [140,240us) on one thread, with an
# inner span [110,128) inside the first. The device's first gap
# [100,150) has its midpoint 125 inside inner_work; its second gap
# [200,300) has its midpoint 250 after the last span has closed.
HOST = plane(2, "/host:CPU", [
    line(7, "engine-loop", T0_NS, [
        event(1, 0 * US, 130 * US),
        event(2, 110 * US, 18 * US),
        event(1, 140 * US, 100 * US),
    ]),
], {1: "engine_step", 2: "inner_work"})

XSPACE = _msg(1, DEVICE) + _msg(1, HOST)

if __name__ == "__main__":
    out = Path(__file__).resolve().parent / "two_ops.xplane.pb"
    out.write_bytes(XSPACE)
    print(out, len(XSPACE), "bytes")
