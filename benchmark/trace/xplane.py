"""Read a profiler trace (``.xplane.pb``) into plain Python.

``jax.profiler.ProfileData`` is the only reader used, so nothing beyond JAX
is needed where the benchmark runs. What comes out is the little the
reduction (``reduce.py``) needs: per device the operation events and the
executable ("module") events, and the host's annotation events, all as
``(name, start_ns, duration_ns)`` on the trace's one clock.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

Event = tuple[str, float, float]  # label, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

# The chip names an operation event by its whole HLO instruction:
# ``%bn1.21 = (bf16[64,64,64,2048]{...}, ...) custom-call(...), custom_call_
# target="tpu_custom_call", ...``. The label keeps what identifies it.
_HLO = re.compile(r"^%?(?P<instr>\S+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def label(name: str) -> str:
    """``<opcode> <instruction> [<first result shape>] [<custom-call
    target>]`` from the event's name: HLO text on the chip, a bare
    instruction name (``fusion.12``) elsewhere. A label passes unchanged."""
    m = _HLO.match(name)
    if not m:
        return name if " " in name else f"{re.sub(r'[.][0-9]+$', '', name)} {name}"
    rest = " " + m.group("rest")
    opcode = _OPCODE.search(rest)
    parts = [opcode.group(1) if opcode else "?", m.group("instr")]
    shape = _SHAPE.search(rest)
    if shape:
        parts.append(shape.group(0))
    target = _TARGET.search(rest)
    if target:
        parts.append(target.group(1))
    return " ".join(parts)


@dataclass
class Device:
    ops: list[Event] = field(default_factory=list)  # nested: see reduce.work
    async_ops: list[Event] = field(default_factory=list)  # copies, collectives in flight
    modules: list[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: dict[int, Device] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)


# Host lines are read for the program's span names only, and a line that shows
# none of them in its first events is given up: the runtime's transfer threads
# wrote 8 million ``Transpose`` events in 16 s of the streaming cell (PR 22).
_HOST_LINE_PROBE = 10_000


def from_planes(planes, host_names: set[str]) -> Trace:
    """``planes``: objects with ``name`` and ``lines``; lines with ``name``
    and ``events``; events with ``name``, ``start_ns``, ``duration_ns`` —
    what ``ProfileData.planes`` yields. Of the host's events only those
    named in ``host_names`` (the program's span names) are kept."""
    trace = Trace()
    for plane in planes:
        device = DEVICE_PLANE.match(plane.name)
        if device:
            dev = trace.devices.setdefault(int(device.group(1)), Device())
            labels: dict[str, str] = {}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev.modules.extend((e.name, e.start_ns, e.duration_ns) for e in line.events)
                    continue
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                target = dev.ops if line.name == OPS_LINE else dev.async_ops
                for e in line.events:
                    name = e.name
                    if name not in labels:
                        labels[name] = label(name)
                    target.append((labels[name], e.start_ns, e.duration_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                found = 0
                for k, e in enumerate(line.events):
                    if e.name in host_names:
                        found += 1
                        trace.host.append((e.name, e.start_ns, e.duration_ns))
                    elif not found and k >= _HOST_LINE_PROBE:
                        break
    for dev in trace.devices.values():
        dev.ops.sort(key=lambda e: (e[1], -e[2]))
        dev.async_ops.sort(key=lambda e: e[1])
        dev.modules.sort(key=lambda e: e[1])
    trace.host.sort(key=lambda e: e[1])
    return trace


def read(path: str, host_names: set[str] = frozenset()) -> Trace:
    from jax.profiler import ProfileData

    return from_planes(ProfileData.from_file(path).planes, set(host_names))
