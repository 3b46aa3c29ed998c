"""Device trace: milliseconds per optimizer step from the start to the end
of every collective operation on chip 0 (async pairs from ``-start`` to
``-done``), overlapping spans counted once."""

from benchmark.trace import reduce


def read(obs, trace):
    if trace is None or 0 not in trace.devices:
        return None
    got = reduce.collectives(trace, 0, obs["steps_per_program"])
    return None if got is None else got[0] / 1e6
