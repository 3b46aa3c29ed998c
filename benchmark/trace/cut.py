"""Cut a recorded trace down to one whole execution of the step program
(plus the ragged ends of its neighbours) so it can be committed as the
reduction's test fixture: ``python -m benchmark.trace.cut IN.xplane.pb
OUT.xplane.pb [host-span-name ...]``. Host events are kept only under the
given names (the program's span names); by default ``step`` and ``ingest``.
"""

from __future__ import annotations

import sys

from benchmark.trace import encode, reduce, xplane


def cut(trace: xplane.Trace, margin: float = 0.1) -> xplane.Trace:
    first = min(trace.devices)
    runs = reduce.step_program(trace, first)
    if not runs:
        raise ValueError("no whole execution of the step program in the trace")
    _, start, dur = runs[len(runs) // 2]
    lo, hi = start - margin * dur, start + (1 + margin) * dur

    def inside(events):
        return [
            (name, max(s, lo), min(s + dur, hi) - max(s, lo))
            for name, s, dur in events
            if s + dur > lo and s < hi
        ]

    out = xplane.Trace()
    for ordinal, dev in trace.devices.items():
        out.devices[ordinal] = xplane.Device(
            ops=inside(dev.ops), async_ops=inside(dev.async_ops), modules=inside(dev.modules)
        )
    out.host = inside(trace.host)
    return out


if __name__ == "__main__":
    src, dst, *names = sys.argv[1:]
    encode.write(cut(xplane.read(src, set(names) or {"step", "ingest"})), dst)
