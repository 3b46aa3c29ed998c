"""Cut a recorded trace down to a test fixture that KEEPS what the scope
and clock readers need (``cut.py`` and ``encode.py`` write names and times
only): ``python -m benchmark.trace.cut_scoped IN.xplane.pb IN.spans.json
OUT.xplane.pb OUT.spans.json``.

Kept, beside one whole execution of the step program and the ragged ends of
its neighbours (``cut.cut``): on every operation of the step program its
scope path and program, as the stats ``tf_op`` and ``program_id`` of the
event's metadata, where the chip puts them; the program's annotations in the
host plane; the ``Task Environment`` plane's ``profile_start_time``; and of
the program's span file the spans that lie inside the cut, with its
``otherData``.
"""

from __future__ import annotations

import json
import sys

from benchmark.trace import cut, hostclock, reduce, scopes, xplane
from benchmark.trace.encode import _bytes, _int, _varint
from benchmark.trace.xplane import ASYNC_LINE, HOST_PLANE, MODULES_LINE, OPS_LINE

TF_OP, PROGRAM_ID, PROFILE_START = 1, 2, 3  # stat metadata ids of a plane


def _stat(metadata_id: int, value) -> bytes:
    """XStat: metadata_id=1, uint64_value=3, str_value=5."""
    body = _int(1, metadata_id)
    return body + (_bytes(5, value.encode()) if isinstance(value, str) else _int(3, value))


def _plane(plane_id: int, name: str, lines: dict, t0: float, stats_of=None, plane_stats=b"") -> bytes:
    """``encode._plane`` plus stat metadata (field 5), stats on the event
    metadata (XEventMetadata.stats=5) and stats of the plane (field 6)."""
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
        message = _int(1, meta) + _bytes(2, ev_name.encode())
        for metadata_id, value in (stats_of(ev_name) if stats_of else ()):
            message += _bytes(5, _stat(metadata_id, value))
        body += _bytes(4, _int(1, meta) + _bytes(2, message))
    for metadata_id, stat_name in ((TF_OP, "tf_op"), (PROGRAM_ID, "program_id"),
                                   (PROFILE_START, "profile_start_time")):
        entry = _int(1, metadata_id) + _bytes(2, stat_name.encode())
        body += _bytes(5, _int(1, metadata_id) + _bytes(2, entry))
    return body + plane_stats


def encode(trace: xplane.Trace, tables: dict[int, dict[str, str]], program: int,
           profile_start_ns: int) -> bytes:
    """The cut ``trace`` as an XSpace; ``tables[device]`` is ``{instruction:
    scope path}`` of the step program ``program``. Times stay the trace's
    own (nanoseconds from ``profile_start_ns``); lines start at 0."""
    space = b""
    for plane_id, (ordinal, dev) in enumerate(sorted(trace.devices.items()), start=1):
        paths = tables.get(ordinal, {})

        def stats_of(label: str, paths=paths):
            path = paths.get(scopes.instruction_of(label))
            return [(TF_OP, path + ":"), (PROGRAM_ID, program)] if path else []

        space += _bytes(1, _plane(
            plane_id, f"/device:TPU:{ordinal}",
            {OPS_LINE: dev.ops, ASYNC_LINE: dev.async_ops, MODULES_LINE: dev.modules},
            0.0, stats_of,
        ))
    n = len(trace.devices)
    space += _bytes(1, _plane(n + 1, HOST_PLANE, {"host": trace.host}, 0.0))
    space += _bytes(1, _plane(
        n + 2, "Task Environment", {}, 0.0,
        plane_stats=_bytes(6, _stat(PROFILE_START, profile_start_ns)),
    ))
    return space


def main(src: str, spans_src: str, dst: str, spans_dst: str) -> None:
    with open(spans_src) as f:
        spans = json.load(f)
    names = {e["name"] for e in spans["traceEvents"]}
    whole = xplane.read(src, names)
    small = cut.cut(whole)
    runs = reduce.step_program(whole, min(whole.devices))
    program = int(scopes._PROGRAM_ID.search(runs[0][0]).group(1))
    tables = {d: scopes.table(raw, runs) for d, raw in scopes.read(src).items()}
    with open(dst, "wb") as f:
        f.write(encode(small, tables, program, hostclock.profile_start_ns(src)))
    # The spans inside the cut, found on the trace's clock.
    obs = {"spans": spans["traceEvents"], "flags": {"trace-file": spans_src}, "xplane": src}
    shift = hostclock.offset_ns(obs)
    lo, hi = reduce.window(small)
    spans["traceEvents"] = [
        e for e in spans["traceEvents"]
        if e.get("ph") == "X" and e["ts"] * 1e3 + shift < hi
        and (e["ts"] + e["dur"]) * 1e3 + shift > lo
    ]
    with open(spans_dst, "w") as f:
        json.dump(spans, f, separators=(",", ":"))


if __name__ == "__main__":
    main(*sys.argv[1:5])
