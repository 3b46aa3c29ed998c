"""From a trace's events to numbers. Pure functions over intervals, so the
test feeds them hand-made events as well as the recorded trace.

Conventions. An interval is ``(start, end)`` in nanoseconds. A device's
operation line holds nested events: a ``while`` spans the operations of its
body. Control flow (``while``, ``conditional``, ``call``) is no work of its
own and never counts, so it cannot hide the idle time inside it; every other
event of non-zero length does, and a union of intervals counts no nanosecond
twice. (Zero-length marker events sit inside long fusions on the chip: telling
containers by "another event starts inside" dropped 6 % of ViT's step, PR 22.)
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmark.trace.xplane import Event, Trace

Interval = tuple[float, float]

# Opcodes of cross-chip communication at the head of an event's label
# (xplane.label); async pairs end in -start / -done.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)(-start|-done)? "
)
# A collective the compiler wrapped (say, into a fusion) keeps its kind in
# the instruction's name, the label's second word.
COLLECTIVE_NAME = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
MOSAIC = re.compile(r"^custom-call .*tpu_custom_call")


def collective(label: str):
    """``(kind, phase)`` of a communication event — phase ``-start``,
    ``-done`` or None — and None for any other event."""
    m = COLLECTIVE.match(label)
    if m:
        return m.group(1), m.group(2)
    words = label.split(" ", 2)
    m = COLLECTIVE_NAME.search(words[1]) if len(words) > 1 else None
    return (m.group(1), None) if m else None


def merge(intervals: list[Interval]) -> list[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: list[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        elif end > start:
            out.append((start, end))
    return out


def length(merged: list[Interval]) -> float:
    return sum(end - start for start, end in merged)


def clip(merged: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def overlap(a: list[Interval], b: list[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


CONTROL_FLOW = {"while", "conditional", "call"}


def work(events: list[Event]) -> list[Event]:
    """The events in which the device did something: non-zero length, not
    control flow."""
    return [e for e in events if e[2] > 0 and e[0].split(" ", 1)[0] not in CONTROL_FLOW]


def self_times(events: list[Event]) -> list[tuple[str, float]]:
    """(label, nanoseconds not covered by an event nested inside it) for
    ``events`` sorted by start, outermost first on ties."""
    out: list[list] = []
    stack: list[tuple[int, float]] = []  # (index into out, end)
    for name, start, dur in events:
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= dur
        out.append([name, dur])
        stack.append((len(out) - 1, start + dur))
    return [(name, max(ns, 0.0)) for name, ns in out]


def intervals(events: list[Event]) -> list[Interval]:
    return [(start, start + dur) for _, start, dur in events]


def window(trace: Trace) -> Interval:
    """First to last device event (operation or program) over all chips:
    the traced steady window, without the profiler's own start-up and
    shut-down."""
    events = [e for d in trace.devices.values() for e in d.ops + d.modules]
    if not events:
        raise ValueError("the trace holds no device event")
    return min(s for _, s, _ in events), max(s + dur for _, s, dur in events)


def busy(trace: Trace, device: int, lo: float, hi: float) -> float:
    """Nanoseconds in [lo, hi] in which an operation ran on ``device``."""
    return length(clip(merge(intervals(work(trace.devices[device].ops))), lo, hi))


def busy_and_window_s(trace: Trace) -> tuple[float, float]:
    """(busy seconds averaged over the chips, window seconds)."""
    lo, hi = window(trace)
    per_chip = [busy(trace, d, lo, hi) for d in trace.devices]
    return sum(per_chip) / len(per_chip) / 1e9, (hi - lo) / 1e9


def program_runs(trace: Trace, device: int) -> list[Event]:
    """Every execution in the trace of the program that took most device
    time — the train step, or the scanned epoch — in order of start, those
    the trace's start or end cut short among them."""
    by_name: dict[str, list[Event]] = defaultdict(list)
    for event in trace.devices[device].modules:
        by_name[event[0]].append(event)
    if not by_name:
        return []
    runs = max(by_name.values(), key=lambda evs: sum(e[2] for e in evs))
    return sorted(runs, key=lambda e: e[1])


def step_program(trace: Trace, device: int) -> list[Event]:
    """The executions of ``program_runs`` that lie WHOLLY inside the trace:
    one the trace's start or end cut short is told by its length (under 98 %
    of the upper quartile's; whole executions of one program differ by far
    less) and left out."""
    runs = program_runs(trace, device)
    if not runs:
        return []
    typical = sorted(e[2] for e in runs)[int(0.75 * len(runs))]
    return [e for e in runs if e[2] >= 0.98 * typical]


def per_step(trace: Trace, device: int, steps_per_program: int, pick=None):
    """(nanoseconds per optimizer step, steps counted) in which an operation
    that ``pick`` selects (any, when None) ran, inside whole executions of
    the step program. None when the trace holds no whole execution."""
    runs = step_program(trace, device)
    if not runs:
        return None
    ops = work(trace.devices[device].ops)
    if pick is not None:
        ops = [e for e in ops if pick(e[0])]
    merged = merge(intervals(ops))
    total = sum(length(clip(merged, s, s + dur)) for _, s, dur in runs)
    steps = len(runs) * steps_per_program
    return total / steps, steps


def collectives(trace: Trace, device: int, steps_per_program: int):
    """Per optimizer step, nanoseconds of (all communication, exposed
    communication). A collective's span runs from its start to its end —
    for an async pair from ``-start`` to the matching ``-done``, which is
    also what the device's async line shows in one event — and it is
    EXPOSED while no other operation runs on that device. None without a
    whole execution of the step program."""
    runs = step_program(trace, device)
    if not runs:
        return None
    ops = work(trace.devices[device].ops)
    compute = merge(intervals([e for e in ops if not collective(e[0])]))
    spans: list[Interval] = [
        (start, start + dur)
        for name, start, dur in trace.devices[device].async_ops
        if collective(name)
    ]
    pending: dict[str, list[float]] = defaultdict(list)
    for name, start, dur in ops:
        found = collective(name)
        if not found:
            continue
        kind, phase = found
        if phase == "-start":
            pending[kind].append(start)
            spans.append((start, start + dur))
        elif phase == "-done" and pending[kind]:
            spans.append((pending[kind].pop(0), start + dur))
        else:
            spans.append((start, start + dur))
    comm = merge(spans)
    total = exposed = 0.0
    for _, s, dur in runs:
        inside = clip(comm, s, s + dur)
        total += length(inside)
        exposed += length(inside) - overlap(inside, compute)
    steps = len(runs) * steps_per_program
    return total / steps, exposed / steps


def top_ops(trace: Trace, device: int, n: int = 10) -> list[list]:
    """The ``n`` operations with most device seconds of their own (what is
    nested inside one is not counted to it), ``[label, seconds]``."""
    totals: dict[str, float] = defaultdict(float)
    for name, ns in self_times(trace.devices[device].ops):
        if name.split(" ", 1)[0] not in CONTROL_FLOW:
            totals[name] += ns
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, device: int, n: int = 10) -> list[list]:
    """Idle seconds of ``device`` inside the window, split by what the host
    was doing: each gap's nanoseconds go to the host annotations (the
    program's spans, ``trace.host``) that overlap it, innermost first, and
    what no annotation covers goes to ``none``."""
    lo, hi = window(trace)
    busy_at = clip(merge(intervals(work(trace.devices[device].ops))), lo, hi)
    gaps, at = [], lo
    for start, end in busy_at:
        if start > at:
            gaps.append((at, start))
        at = end
    if hi > at:
        gaps.append((at, hi))
    # Shortest annotations first, so a nested span claims its part before
    # the span around it.
    spans = sorted(
        ((name, s, s + dur) for name, s, dur in trace.host if dur > 0),
        key=lambda e: e[2] - e[1],
    )
    totals: dict[str, float] = defaultdict(float)
    for gap in gaps:
        left = [gap]
        for name, s, e in spans:
            if e <= gap[0] or s >= gap[1]:
                continue
            rest = []
            for a, b in left:
                lo2, hi2 = max(a, s), min(b, e)
                if hi2 > lo2:
                    totals[name] += hi2 - lo2
                    if a < lo2:
                        rest.append((a, lo2))
                    if hi2 < b:
                        rest.append((hi2, b))
                else:
                    rest.append((a, b))
            left = rest
        totals["none"] += sum(b - a for a, b in left)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked if ns > 0]
