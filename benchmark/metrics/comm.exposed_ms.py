"""Device trace: the part of ``comm.total_ms`` during which no other
operation ran on chip 0 — communication the step waits for."""

from benchmark.trace import reduce


def read(obs, trace):
    if trace is None or 0 not in trace.devices:
        return None
    got = reduce.collectives(trace, 0, obs["steps_per_program"])
    return None if got is None else got[1] / 1e6
