"""Device trace: milliseconds chip 0 was busy per optimizer step, inside
whole executions of the step program."""

from benchmark.trace import reduce


def read(obs, trace):
    if trace is None or 0 not in trace.devices:
        return None
    got = reduce.per_step(trace, 0, obs["steps_per_program"])
    return None if got is None else got[0] / 1e6
