"""Read the fields ``jax.profiler.ProfileData`` does not surface, straight
from the ``.xplane.pb`` (tsl/profiler/protobuf/xplane.proto), with nothing
beyond the standard library — the reading counterpart of ``encode.py``.

The chip keeps an instruction's ``op_name`` (JAX's name stack), its program
and its source line as stats of the event's METADATA, which ``ProfileData``
events do not show (PR 24: ``event.stats`` holds ``device_offset_ps``,
``device_duration_ps`` and no more); and the wall-clock time at which the
trace's own clock starts as a stat of the ``Task Environment`` plane.

Field numbers. XSpace: planes=1. XPlane: name=2, lines=3, event_metadata=4
and stat_metadata=5 (maps: key=1, value=2), stats=6. XEventMetadata: id=1,
name=2, stats=5. XStatMetadata: id=1, name=2. XStat: metadata_id=1,
uint64_value=3, int64_value=4, str_value=5, ref_value=7 (the string is the
NAME of that stat metadata).
"""

from __future__ import annotations


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    ``memoryview`` for a length-delimited or fixed-width field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {kind} in an xplane message")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(stat, stat_names: dict[int, str]):
    """``(name, value)`` of one XStat; a ``ref_value`` is resolved."""
    got = dict(fields(stat))
    name = stat_names.get(got.get(1), "")
    if 5 in got:
        return name, _text(got[5])
    if 7 in got:
        return name, stat_names.get(got[7], "")
    return name, got.get(3, got.get(4))


def planes(path: str):
    """Per plane of the file: ``(name, plane stats, event metadata)`` —
    stats as ``{name: value}``, event metadata as a list of ``(event name,
    {stat name: value})``. Lines (the events themselves, most of the file)
    are skipped unread: ``ProfileData`` serves those."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, plane in fields(space):
        if number != 1:
            continue
        name, stat_names, plane_stats, metadata = "", {}, [], []
        for field, value in fields(plane):
            if field == 2:
                name = _text(value)
            elif field == 5:
                entry = dict(fields(dict(fields(value))[2]))
                stat_names[entry.get(1, 0)] = _text(entry.get(2, b""))
            elif field == 6:
                plane_stats.append(value)
            elif field == 4:
                metadata.append(dict(fields(value))[2])
        events = []
        for message in metadata:
            event_name, stats = "", {}
            for field, value in fields(message):
                if field == 2:
                    event_name = _text(value)
                elif field == 5:
                    key, got = _stat(value, stat_names)
                    stats[key] = got
            events.append((event_name, stats))
        yield name, dict(_stat(s, stat_names) for s in plane_stats), events
