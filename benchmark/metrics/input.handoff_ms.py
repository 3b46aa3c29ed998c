"""Device trace and program spans on one clock: median milliseconds from the
start of a batch's ``h2d`` span (``device_prefetch`` takes it from the
loader) to the first device operation of the step that trains on it. Holds
the pad, the runtime's layout transposes, the DMA, and any time
``device_prefetch`` keeps a finished batch in its buffer. The steps' own
values are printed: where an epoch has two batches and the first waits for
the second's decode they are of two kinds, and the median of an even number
of them is the mean of the two kinds.

The execution's batch is the ``epoch``/``step`` of the ``step`` span it
ran under (the traced run syncs every step, so an execution lies inside its
own step's span): the one it overlaps most once ``spans.json`` is placed on
the trace's clock by ``hostclock.offset_ns`` (the two origins written down).
Overlap, not "the last span that began before it": a step's first operation
starts a few tenths of a millisecond after its span opens, and the trace's
device timeline sits that far off its host timeline — in two of this PR's
traces a step's first operation read 0.17 to 0.38 ms BEFORE its span opened,
with the written origins within 3 us of the annotations (PR 24)."""

import statistics

from benchmark.trace import hostclock, reduce


def read(obs, trace):
    if trace is None or 0 not in trace.devices:
        return None
    h2d = {
        (e["args"]["epoch"], e["args"]["batch"]): e["ts"] * 1e3
        for e in obs["spans"] if e["name"] == "h2d" and "batch" in e.get("args", {})
    }
    runs = reduce.step_program(trace, 0)
    if not h2d or not runs:
        return None
    shift = hostclock.offset_ns(obs)
    if shift is None:
        return None
    steps = [
        (e["ts"] * 1e3 + shift, e["dur"] * 1e3, (e["args"]["epoch"], e["args"]["step"]))
        for e in obs["spans"] if e["name"] == "step" and "step" in e.get("args", {})
    ]
    ops = reduce.work(trace.devices[0].ops)

    def overlap(start, dur, step):
        return min(start + dur, step[0] + step[1]) - max(start, step[0])

    waits = []
    for _, start, dur in runs:
        first = min((s for _, s, _ in ops if start <= s < start + dur), default=None)
        if first is None or not steps:
            continue
        under = max(steps, key=lambda step: overlap(start, dur, step))
        if overlap(start, dur, under) <= 0 or under[2] not in h2d:
            continue  # under no step span, or one whose batch has no h2d
        waits.append((*under[2], (first - (h2d[under[2]] + shift)) / 1e6))
    print(f"benchmark: handoff by step (epoch, batch, ms): {waits}", flush=True)
    return statistics.median(ms for _, _, ms in waits) if waits else None
