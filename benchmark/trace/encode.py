"""Write a ``Trace`` back out as an ``.xplane.pb`` that
``jax.profiler.ProfileData`` reads — the few fields of the XSpace protobuf
(tsl/profiler/protobuf/xplane.proto) the reader uses, encoded by hand so
that cutting a recorded trace down to a test fixture, or building one from
hand-made events, needs nothing beyond the standard library.
"""

from __future__ import annotations

from benchmark.trace.xplane import (
    ASYNC_LINE, HOST_PLANE, MODULES_LINE, OPS_LINE, Event, Trace,
)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte = n & 0x7F
        n >>= 7
        if n:
            out.append(byte | 0x80)
        else:
            out.append(byte)
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(plane_id: int, name: str, lines: dict[str, list[Event]], t0: float) -> bytes:
    """XPlane: id=1, name=2, lines=3, event_metadata=4 (map id -> {id=1,
    name=2}). XLine: id=1, name=2, timestamp_ns=3, events=4. XEvent:
    metadata_id=1, offset_ps=2, duration_ps=3."""
    ids: dict[str, int] = {}
    body = _int(1, plane_id) + _bytes(2, name.encode())
    for line_id, (line_name, events) in enumerate(lines.items(), start=1):
        line = _int(1, line_id) + _bytes(2, line_name.encode()) + _int(3, int(t0))
        for ev_name, start, dur in events:
            meta = ids.setdefault(ev_name, len(ids) + 1)
            line += _bytes(
                4,
                _int(1, meta) + _int(2, int(round((start - t0) * 1000)))
                + _int(3, int(round(dur * 1000))),
            )
        body += _bytes(3, line)
    for ev_name, meta in ids.items():
        entry = _int(1, meta) + _bytes(2, _int(1, meta) + _bytes(2, ev_name.encode()))
        body += _bytes(4, entry)
    return body


def encode(trace: Trace) -> bytes:
    """XSpace: planes=1. Every line shares one origin, the earliest event."""
    starts = [e[1] for d in trace.devices.values() for e in d.ops + d.async_ops + d.modules]
    starts += [e[1] for e in trace.host]
    t0 = min(starts) if starts else 0.0
    space = b""
    for plane_id, (ordinal, dev) in enumerate(sorted(trace.devices.items()), start=1):
        space += _bytes(
            1,
            _plane(plane_id, f"/device:TPU:{ordinal}",
                   {OPS_LINE: dev.ops, ASYNC_LINE: dev.async_ops, MODULES_LINE: dev.modules}, t0),
        )
    space += _bytes(1, _plane(len(trace.devices) + 1, HOST_PLANE, {"host": trace.host}, t0))
    return space


def write(trace: Trace, path: str) -> None:
    with open(path, "wb") as f:
        f.write(encode(trace))
